"""The device-resident half of ``plan="device"``.

The cpu plan probes a frozen level with one host ``searchsorted`` of its
fused arena and gathers every hit row on the host.  This module keeps the
heavy state — the fused :class:`~repro.core.frozen.ProbeArena`
key/offset/window arrays — *resident* on the accelerator and runs the
probe binary search (a jitted XLA program over the HBM-resident arrays)
there; the probe's extents are then grouped over the text runs of the
arena's run table (:class:`ResidentProbe`), and the small groups' rows
are gathered on the device for the grouped sweep (a Pallas kernel, run
from ``repro.core.query.sweep_groups``).  So per batch only

* up:   the packed probe keys (B*k few-byte words) and the small-group
  gather index grids,
* down: the CSR probe extents and the compressed coverage grids + stripe
  boundaries the final blocks are read from

cross the bus — never the arena, never the window rows.

Residency
---------
:func:`device_arena` caches a :class:`DeviceArena` on the index instance,
keyed by the *identity* of its host ``ProbeArena``: a ``SearchIndex`` is
immutable, and every path that changes the store generation
(``LiveIndex.compact``/``promote_sealed``) swaps in a NEW ``SearchIndex``,
so the upload happens at most once per store generation and invalidation
is automatic.  An arena the device probe cannot address (a CSR extent past
int32) raises :class:`DeviceArenaError`: the device plan never quietly
serves a batch on the host.  The mutable live delta level never comes
through here — ``repro.core.query._probe_level`` gives mutable levels the
host dict probe, which is what keeps live serving correct between
compactions.

Bit parity
----------
Every device stage has exact integer semantics (the binary search and hit
detect are u32 lexicographic compares, the sweep kernel is integer-exact
by construction — see :mod:`repro.kernels.sweep_grid`), and the plan's
default sketch stage is the exact host path, so ``plan="device"`` is
bit-identical to ``plan="cpu"`` — gated in ``tests/test_device_plan.py``.

``transfer_stats()`` exposes logical host<->device byte counters (what
crosses the bus on a real accelerator; in interpret mode the same arrays
flow, uncounted copies aside) for the residency tests and the roofline
benchmark's fused-pipeline row, plus work counters: ``batches`` (probes
of a resident arena, one per batch and frozen level) and, counted by
``query.sweep_groups`` on either plan, ``sweep_launches`` (device sweep
launches), ``probe_windows`` (windows the probe hit, the sum of its
extents), ``probe_text_runs`` (the text runs of those extents, the run
grouping's input), ``groups_kept`` ((query, text) groups with at least
⌈kθ⌉ distinct coordinates), ``host_large_groups`` (kept groups of more
than ``_SMALL_GROUP_MAX`` windows, which are swept on the host by design),
``host_large_windows`` (the windows of those groups) and
``host_large_rejected`` (those of them that the exact test in front of
the host sweep, ``query._large_groups_hot``, finds can hold no cell
covered ⌈kθ⌉ times, so they are not swept).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .frozen import MODE_PACKED, PACK_SHIFT
from .query import KeptGroups
from .spans import span

__all__ = ["DeviceArena", "DeviceArenaError", "ResidentProbe",
           "device_arena", "resident_probe", "transfer_stats",
           "reset_transfer_stats"]

_I32_MAX = np.iinfo(np.int32).max

# logical host<->device transfer accounting (bytes that cross the bus on
# a real accelerator).  arena_* count the once-per-generation residency
# upload; h2d/d2h count the per-batch steady-state traffic; the rest
# count device probes, device sweep launches and the grouping's work.
_STATS = {"arena_uploads": 0, "arena_bytes": 0,
          "h2d_bytes": 0, "d2h_bytes": 0, "batches": 0,
          "sweep_launches": 0, "probe_windows": 0, "probe_text_runs": 0,
          "groups_kept": 0,
          "host_large_groups": 0, "host_large_windows": 0,
          "host_large_rejected": 0}


def transfer_stats() -> dict:
    """A snapshot of the module's transfer counters."""
    return dict(_STATS)


def reset_transfer_stats() -> None:
    for key in _STATS:
        _STATS[key] = 0


def add_counts(**counts: int) -> None:
    """Add to the module's counters (``query.sweep_groups`` reports the
    sweep's bytes and the work counters through here)."""
    for key, n in counts.items():
        _STATS[key] += int(n)


class DeviceArenaError(RuntimeError):
    """A store's arena cannot go resident on the device."""


@dataclass
class DeviceArena:
    """One store generation's ProbeArena, resident on the accelerator.

    Keys are split into u32 (hi, lo) halves plus the coordinate tag word
    (the probe's comparison format); offsets are narrowed to int32
    (guarded at build — an arena too large raises ``DeviceArenaError``);
    ``win_rect`` holds only the (a, b, c, d) rectangle columns.  The
    text-id column never needs the bus: the host keeps it as a run table,
    ``run_start``/``run_tid``, the maximal stretches of one text id
    inside one slot's extent (every extent bound is a run bound), which
    the run grouping groups by.  An empty arena is resident with
    ``n == 0`` and answers every probe with a miss.

    It is also the row source of its level's :class:`~repro.core.query.
    KeptGroups`: the host reads rows off ``windows``, the arena's mmap,
    and the device gathers them from ``win_rect``.
    """

    mode: str
    n: int                    # arena slots
    khi: object               # jnp u32 (n,)
    klo: object               # jnp u32 (n,)
    ktag: object              # jnp u32 (n,)
    offsets: object           # jnp i32 (n + 1,)
    win_rect: object          # jnp i32 (nwin, 4)
    nbytes: int
    run_start: np.ndarray     # host i32 (nruns + 1,) first row of each run
    run_tid: np.ndarray       # host i32 (nruns,) text id of each run
    windows: np.ndarray       # host (nwin, 5) window rows (the mmap)

    reads_mmap = True

    def read(self, rows: np.ndarray) -> np.ndarray:
        """The (a, b, c, d) rectangles of the given rows, off the mmap."""
        return np.asarray(self.windows[rows, 1:5])

    def device_rects(self, idx: np.ndarray) -> tuple[object, int]:
        """The rectangles of an index grid, gathered on the device from
        the resident columns (only the grid goes up); and its bytes."""
        import jax.numpy as jnp
        idx = np.asarray(idx, np.int32)
        return jnp.take(self.win_rect, jnp.asarray(idx), axis=0), idx.nbytes


def _text_runs(tid: np.ndarray, offsets: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
    """The run table of a text-id column cut into CSR extents: the
    int32 bounds of every maximal stretch of one text id inside one
    extent, and each stretch's text id."""
    nwin = len(tid)
    bound = np.empty(nwin, bool)
    bound[:1] = True
    np.not_equal(tid[1:], tid[:-1], out=bound[1:])
    cuts = offsets[:-1]
    bound[cuts[cuts < nwin]] = True
    run_start = np.append(np.flatnonzero(bound), nwin).astype(np.int32)
    return run_start, tid[run_start[:-1]]


def _build_device_arena(arena) -> DeviceArena:
    """Upload one ProbeArena.  Raises :class:`DeviceArenaError` when its
    CSR extent overflows the device probe's int32 offsets."""
    n = len(arena.keys)
    nwin = int(arena.offsets[-1])
    if nwin > _I32_MAX:
        raise DeviceArenaError(
            f"the arena holds {nwin} windows, past the device probe's int32 "
            f"offsets (at most {_I32_MAX}); serve this store with "
            'plan="cpu" or split it into shards')
    import jax.numpy as jnp

    from ..kernels.probe_arena import _split_u64
    khi, klo = _split_u64(np.asarray(arena.keys))
    if arena.mode == MODE_PACKED:
        ktag = np.zeros(n, np.uint32)
    else:
        ktag = np.ascontiguousarray(arena.coords, np.uint32)
    offsets = np.asarray(arena.offsets, np.int32)
    windows = np.asarray(arena.windows)
    rect = np.ascontiguousarray(windows[:, 1:5], np.int32)
    run_start, run_tid = _text_runs(
        np.ascontiguousarray(windows[:, 0], np.int32), offsets)
    dev = DeviceArena(
        mode=arena.mode, n=n,
        khi=jnp.asarray(khi), klo=jnp.asarray(klo), ktag=jnp.asarray(ktag),
        offsets=jnp.asarray(offsets), win_rect=jnp.asarray(rect),
        nbytes=(khi.nbytes + klo.nbytes + ktag.nbytes + offsets.nbytes +
                rect.nbytes),
        run_start=run_start, run_tid=run_tid, windows=arena.windows)
    _STATS["arena_uploads"] += 1
    _STATS["arena_bytes"] += dev.nbytes
    return dev


def device_arena(index) -> DeviceArena:
    """The index's resident arena, uploading on first use and caching on
    the index instance (``SearchIndex._device_arena``).  The cache is
    keyed by the host ``ProbeArena``'s identity, so a promotion/compaction
    (which swaps in a new ``SearchIndex`` and so a new arena) re-uploads
    exactly once and stale residency can never serve a new generation.
    Raises :class:`DeviceArenaError` when the arena cannot go resident."""
    arena = index.arena()
    cached = getattr(index, "_device_arena", None)
    if cached is not None and cached[0] is arena:
        return cached[1]
    dev = _build_device_arena(arena)
    try:
        index._device_arena = (arena, dev)
    except (AttributeError, TypeError):
        pass                                 # slotted/frozen duck: no cache
    return dev


# --------------------------------------------------------------------------
# resident probe
# --------------------------------------------------------------------------


def _probe_jit_factory():
    """Build the jitted device probe lazily so importing this module never
    pays a jax import."""
    import jax
    import jax.numpy as jnp

    from ..kernels.probe_arena import _arena_search

    @jax.jit
    def probe(khi, klo, ktag, offsets, qhi, qlo, qtag, valid):
        n = khi.shape[0]
        pos = _arena_search(khi, klo, ktag, qhi, qlo, qtag)
        safe = jnp.minimum(pos, n - 1)
        # generic (hi, lo, tag) equality covers both arena modes: packed
        # arenas carry all-zero tags (and all-zero probe tags), coord
        # arenas compare the coordinate word — exactly the host hit detect
        hit = valid & (pos < n) & \
            (jnp.take(khi, safe) == qhi) & (jnp.take(klo, safe) == qlo) & \
            (jnp.take(ktag, safe) == qtag)
        starts = jnp.where(hit, jnp.take(offsets, safe), 0)
        ends = jnp.where(hit, jnp.take(offsets, safe + 1), 0)
        return starts, ends

    return probe


_PROBE_JIT = None


def _encode_queries(mode: str, pkeys: np.ndarray, coords: np.ndarray,
                    valid: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side probe re-keying, identical to ``ProbeArena.probe``: packed
    arenas fold the coordinate into the key's top bits, coord arenas carry
    it as the tag word."""
    from ..kernels.probe_arena import _split_u64
    if mode == MODE_PACKED:
        q = (coords.astype(np.uint64) << np.uint64(PACK_SHIFT)) | \
            np.where(valid, pkeys, 0)
        qhi, qlo = _split_u64(q)
        qtag = np.zeros(len(q), np.uint32)
    else:
        qhi, qlo = _split_u64(pkeys)
        qtag = coords.astype(np.uint32)
    return qhi, qlo, qtag


def _device_probe(da: DeviceArena, pkeys, coords, valid,
                  times: dict | None = None
                  ) -> tuple[np.ndarray, np.ndarray]:
    """The probe call on the resident arena; the call through its
    blocking read-back is the ``probe.device`` span (timed into
    ``times`` when given)."""
    global _PROBE_JIT
    if _PROBE_JIT is None:
        _PROBE_JIT = _probe_jit_factory()
    import jax.numpy as jnp
    _STATS["batches"] += 1
    if len(pkeys) == 0 or da.n == 0:
        z = np.zeros(len(pkeys), np.int64)
        return z, z
    qhi, qlo, qtag = _encode_queries(da.mode, pkeys, coords, valid)
    valid = np.ascontiguousarray(valid, bool)
    with span(times, "probe.device"):
        starts, ends = _PROBE_JIT(
            da.khi, da.klo, da.ktag, da.offsets,
            jnp.asarray(qhi), jnp.asarray(qlo), jnp.asarray(qtag),
            jnp.asarray(valid))
        starts = np.asarray(starts, np.int64)
        ends = np.asarray(ends, np.int64)
    _STATS["h2d_bytes"] += (qhi.nbytes + qlo.nbytes + qtag.nbytes +
                            valid.nbytes)
    _STATS["d2h_bytes"] += 2 * len(pkeys) * 4        # i32 starts + ends
    return starts, ends


def resident_probe(index, pkeys, coords, valid
                   ) -> tuple[np.ndarray, np.ndarray]:
    """``ProbeArena.probe``-identical (starts, ends), probing the resident
    device arena."""
    return _device_probe(device_arena(index), pkeys, coords, valid)


# --------------------------------------------------------------------------
# a frozen level under the device plan: resident probe, run grouping
# --------------------------------------------------------------------------


class ResidentProbe:
    """A frozen level probed on its resident arena (the device-plan choice
    of ``query._probe_level``): the device probe's CSR extents, then
    (``gather``) the text runs of those extents from the arena's run
    table, then (``kept``) the run grouping.  No window row is read on
    the host until the sweep reads the large groups'."""

    def __init__(self, index, sketches, times: dict | None = None):
        arena = index.arena()
        self.k = arena.k
        pkeys, coords, valid = arena.encode_batch(sketches)
        self.da = device_arena(index)
        self.starts, self.ends = _device_probe(self.da, pkeys, coords, valid,
                                               times)

    def gather(self, times: dict | None) -> None:
        with span(times, "probe.gather"):
            self.runs = _probe_runs(self.da.run_start, self.starts,
                                    self.ends)

    def kept(self, m: int) -> KeptGroups:
        return _kept_groups(self.da, *self.runs, self.k, m,
                            hits=int((self.ends - self.starts).sum()))


def _probe_runs(run_start: np.ndarray, starts: np.ndarray, ends: np.ndarray
                ) -> tuple[np.ndarray, np.ndarray]:
    """The text runs of each probe's extent ``[starts, ends)``, in gather
    order (probe, then row): int32 (probe ids, run ids).  Extent bounds
    are run bounds, so one ``searchsorted`` per bound finds them."""
    dt = run_start.dtype   # mixed dtypes would copy the table per batch
    lo = np.searchsorted(run_start, starts.astype(dt))
    n = np.searchsorted(run_start, ends.astype(dt)) - lo
    probe_r = np.repeat(np.arange(len(starts), dtype=np.int32), n)
    return probe_r, _ranges32(lo, n)


def _ranges32(lo: np.ndarray, n: np.ndarray) -> np.ndarray:
    """``_concat_ranges`` in int32: the ranges ``[lo, lo + n)`` back to
    back.  Half the bytes of an int64 index; one past 32 MiB is mapped
    fresh, a page fault per page, on every batch."""
    first = (lo - np.cumsum(n) + n).astype(np.int32)
    return np.repeat(first, n) + np.arange(int(n.sum()), dtype=np.int32)


def _kept_groups(da: DeviceArena, probe_r: np.ndarray, run_r: np.ndarray,
                 k: int, m: int, *, hits: int) -> KeptGroups:
    """The run grouping: (query, text) grouping over text runs, and the
    rows of the groups with at least m distinct coordinates.

    A stable sort of the runs by (query id, text id) keeps gather order
    inside each group, which is coordinate order (probe ids are
    query-major) and then row order, so expanding the kept runs gives
    each group's rows in the order of ``query._row_groups``'s stable row
    sort.  A group's distinct coordinates are its coordinate steps,
    counted over runs; one text may lie in several runs of one extent.
    Returns the kept groups with int32 row ids and coordinate ids, and
    ``da`` as their row source; ``hits`` is the windows the probe hit.
    """
    qid, cid = probe_r // k, probe_r % k
    tid = da.run_tid[run_r]
    key = qid.astype(np.int64) << 32 | tid
    # timsort merges the k text-ascending runs of each query
    order = np.argsort(key, kind="stable")
    key, cid, run_r = key[order], cid[order], run_r[order]
    n = len(key)
    step = np.ones(n, bool)
    np.not_equal(key[1:], key[:-1], out=step[1:])
    g_lo = np.flatnonzero(step)
    np.not_equal(cid[1:], cid[:-1], out=step[1:])
    step[g_lo] = True
    distinct = np.add.reduceat(step, g_lo)
    kept = np.flatnonzero(distinct >= m)
    g_n = np.diff(np.append(g_lo, n))[kept]
    at = _ranges32(g_lo[kept], g_n)
    runs, cid = run_r[at], cid[at]
    lo = da.run_start[runs]
    ln = da.run_start[runs + 1] - lo
    sizes = np.add.reduceat(ln.astype(np.int64), np.cumsum(g_n) - g_n)
    head = key[g_lo[kept]]
    return KeptGroups(rows=_ranges32(lo, ln), cids=np.repeat(cid, ln),
                      sizes=sizes, qids=head >> 32, tids=head & 0xFFFFFFFF,
                      distinct=distinct[kept], source=da, hits=hits,
                      runs=len(run_r))
