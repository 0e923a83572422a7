"""Device-side probe of the fused CSR arena.

One call binary-searches every probe key of a batch against the arena's
sorted key array (``repro.core.frozen.ProbeArena``), resident on the
device (``repro.core.device_plan`` builds its jitted probe from
:func:`_arena_search`).  Keys are uint64 on
the host but TPU VPUs have no 64-bit integer lanes, so arena and probe
keys are split into (hi, lo) uint32 halves and compared lexicographically;
the coordinate tag of the arena's "coord" mode rides along as a third
comparison word (all-zero in "packed" mode, where the coordinate already
lives in the key's top bits).

Per probe the search returns the leftmost arena slot whose
``(key, coord) >= (probe key, probe coord)`` — exactly the slot the host
path's ``np.searchsorted(..., side="left")`` plus duplicate-run advance
lands on — so hit detection and the CSR offsets/windows gather agree with
the host probe bit for bit.

The search is a jitted XLA program, not a Pallas kernel: a fixed-count
``fori_loop`` of halvings, each one ``jnp.take`` gather per key word over
the HBM-resident arrays.  Nothing is staged through VMEM, so the arena
can be as large as device memory allows (a Pallas kernel would have to
map the whole arena into VMEM, capping it near a million slots, and
Mosaic lowers no 1-D gather).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _lex_less(ahi, alo, atag, bhi, blo, btag):
    """(ahi, alo, atag) < (bhi, blo, btag), all uint32, elementwise."""
    return (ahi < bhi) | ((ahi == bhi) & ((alo < blo) |
                                          ((alo == blo) & (atag < btag))))


@jax.jit
def _arena_search(khi, klo, ktag, qhi, qlo, qtag):
    """Leftmost slot with (khi, klo, ktag) >= (qhi, qlo, qtag) per probe,
    int32 (P,).  The arena (n,) must be non-empty."""
    n = khi.shape[0]
    iters = max(1, int(n).bit_length())      # floor(log2 n) + 1 halvings
    take = lambda a, i: jnp.take(a, i, mode="clip")

    def body(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = (lo + hi) // 2             # < hi <= n, so a valid slot
        safe = jnp.minimum(mid, n - 1)
        less = _lex_less(take(khi, safe), take(klo, safe), take(ktag, safe),
                         qhi, qlo, qtag)
        lo = jnp.where(active & less, mid + 1, lo)
        hi = jnp.where(active & ~less, mid, hi)
        return lo, hi

    lo = jnp.zeros(qhi.shape, jnp.int32)
    hi = jnp.full(qhi.shape, n, jnp.int32)
    lo, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def _split_u64(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    a = np.ascontiguousarray(a, dtype=np.uint64)
    return ((a >> np.uint64(32)).astype(np.uint32), a.astype(np.uint32))
