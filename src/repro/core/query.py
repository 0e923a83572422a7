"""Query processing (Algorithm 2): sketch the query, probe the k inverted
lists, plane-sweep the collided compact windows for cells covered >= ⌈kθ⌉
times (those subsequences have estimated Jaccard >= θ, Eq. 2/Eq. 5).

Two execution paths over the same algorithm:

* ``query``       — one query at a time; works on mutable (dict) and frozen
  indexes alike.
* ``batch_query`` — the serving path: sketches the whole batch at once,
  probes ALL B*k (query, coordinate) pairs against the fused probe arena
  (``repro.core.frozen.ProbeArena``) in ONE ``searchsorted`` + gather
  (``probe_backend="numpy"``; ``"pallas"`` routes the binary search through
  the device search, ``"percoord"`` keeps the legacy per-coordinate probe
  loop, which is also what mutable dict indexes use), and groups the
  collided windows by (query, text) with one lexsort.  The per-group plane
  sweep goes through a grouped dispatcher: the many tiny groups of Zipf
  traffic are batched through one vectorized small-group sweep
  (``sweep="grouped"``, the default) and only large groups fall back to
  the per-group ``_sweep_text``.  Every combination returns block-for-block
  the same results as looping ``query``.
"""

from __future__ import annotations

import math
import time
from collections import defaultdict
from dataclasses import dataclass

import numpy as np

from .frozen import _concat_ranges
from .plan import resolve_plan
from .results import UNSET, QueryOptions, coerce_query_options
from .spans import add_seconds, span


@dataclass
class Alignment:
    """All result subsequences of one data text, as maximal blocks.

    blocks: list of (i_lo, i_hi, j_lo, j_hi) — every T[i..j] with
    i ∈ [i_lo, i_hi], j ∈ [j_lo, j_hi] is a result (0-indexed inclusive).
    """

    text_id: int
    blocks: list[tuple[int, int, int, int]]
    # distinct colliding sketch coordinates (>= ceil(k*theta) whenever
    # blocks is non-empty); ncoords/k estimates the query<->text Jaccard
    ncoords: int | None = None

    def cells(self) -> set[tuple[int, int]]:
        out = set()
        for il, ih, jl, jh in self.blocks:
            for i in range(il, ih + 1):
                for j in range(jl, jh + 1):
                    out.add((i, j))
        return out

    @property
    def num_cells(self) -> int:
        return sum((ih - il + 1) * (jh - jl + 1) for il, ih, jl, jh in self.blocks)


def _sweep_text(windows: list[tuple[int, int, int, int]], m: int
                ) -> list[tuple[int, int, int, int]]:
    """Cells covered by >= m of the given rectangles, as disjoint blocks.

    Coordinate-compressed 2-D difference array + cumulative sums; output
    blocks are maximal runs within each compressed stripe.
    """
    if len(windows) < m:
        return []
    arr = np.asarray(windows, dtype=np.int64)
    a, b, c, d = arr[:, 0], arr[:, 1], arr[:, 2], arr[:, 3]
    xs = np.unique(np.concatenate([a, b + 1]))
    ys = np.unique(np.concatenate([c, d + 1]))
    nx, ny = len(xs), len(ys)
    xi_a = np.searchsorted(xs, a)
    xi_b = np.searchsorted(xs, b + 1)
    yi_c = np.searchsorted(ys, c)
    yi_d = np.searchsorted(ys, d + 1)
    # one bincount scatter of the four +-1 corner pulses (C fast path)
    stride = ny + 1
    pos = np.concatenate([xi_a * stride + yi_c, xi_b * stride + yi_d])
    neg = np.concatenate([xi_a * stride + yi_d, xi_b * stride + yi_c])
    diff = (np.bincount(pos, minlength=(nx + 1) * stride)
            - np.bincount(neg, minlength=(nx + 1) * stride)
            ).reshape(nx + 1, stride).astype(np.int32)
    count = np.cumsum(np.cumsum(diff, axis=0), axis=1)
    # xs[i]..xs[i+1]-1 stripes; the last compressed coord is always an
    # exclusive upper bound (b+1 / d+1), so hot cannot extend past it.
    hot = count[:nx - 1, :ny - 1] >= m
    if not hot.any():
        return []
    # maximal horizontal runs per stripe, vectorized: +1/-1 edges of the
    # zero-padded hot mask mark run starts / one-past-run ends
    hpad = np.zeros((nx - 1, ny + 1), dtype=np.int8)
    hpad[:, 1:ny] = hot
    edges = np.diff(hpad, axis=1)
    rs, cs = np.nonzero(edges == 1)       # run starts (row-major)
    _, ce = np.nonzero(edges == -1)       # aligned exclusive run ends
    return [(int(xs[r]), int(xs[r + 1] - 1), int(ys[c0]), int(ys[c1] - 1))
            for r, c0, c1 in zip(rs, cs, ce)]


def query(index, query_tokens, theta: float
          ) -> list[Alignment]:
    """Near-duplicate text alignment (Definition 1) for one query."""
    k = index.scheme.k
    m = max(1, math.ceil(k * theta))
    sketch = index.scheme.sketch(query_tokens)
    per_text: dict[int, list] = defaultdict(list)
    ncoords: dict[int, int] = defaultdict(int)
    for i in range(k):
        prev = None
        for (tid, a, b, c, d) in index.lookup(i, sketch[i]):
            per_text[tid].append((a, b, c, d))
            if tid != prev:                 # postings are grouped by tid
                ncoords[tid] += 1
                prev = tid
    results = []
    for tid, wins in sorted(per_text.items()):
        # windows from one coordinate are disjoint (a cell's min-hash is
        # unique), so coverage >= m needs >= m distinct coordinates — skip
        # the sweep when that is impossible
        if ncoords[tid] < m:
            continue
        blocks = _sweep_text(wins, m)
        if blocks:
            results.append(Alignment(text_id=int(tid), blocks=blocks,
                                     ncoords=int(ncoords[tid])))
    return results


_SMALL_GROUP_MAX = 32    # windows; larger groups use the per-group sweep
_SMALL_CHUNK_CELLS = 1 << 22   # bound the batched difference-array footprint


def _sweep_small_batch(arr: np.ndarray, sizes: np.ndarray, m: int
                       ) -> list[list[tuple[int, int, int, int]]]:
    """Vectorized ``_sweep_text`` over G small groups at once.

    arr: int64 (G, S, 4) rectangle rows, padded past ``sizes[g]`` with
    anything; returns per-group block lists identical to running
    ``_sweep_text(arr[g, :sizes[g]], m)`` group by group.

    Padding is normalized to zero-width rectangles at each group's max
    boundary and given bincount weight 0, so padded entries contribute no
    coverage and only duplicate existing compressed coordinates.  Duplicate
    boundary values are harmless: searchsorted-left drops every pulse on
    the first duplicate, making later duplicates exact pass-throughs, so
    run starts/ends land on the same coordinate values as the
    ``np.unique``-compressed per-group sweep; zero-width *stripes* are
    masked cold because each stripe emits its own block.
    """
    G, S, _ = arr.shape
    # chunk so the per-chunk difference array stays cache/RAM friendly even
    # when a batch produces tens of thousands of small groups
    per = max(1, _SMALL_CHUNK_CELLS // ((2 * S + 1) * (2 * S + 1)))
    if G > per:
        out = []
        for lo in range(0, G, per):
            out.extend(_sweep_small_batch(arr[lo:lo + per],
                                          sizes[lo:lo + per], m))
        return out
    arr = arr.astype(np.int64, copy=True)
    pad = np.arange(S)[None, :] >= sizes[:, None]            # (G, S)
    a, b, c, d = arr[..., 0], arr[..., 1], arr[..., 2], arr[..., 3]
    bmax = np.where(pad, np.iinfo(np.int64).min, b + 1).max(axis=1)
    dmax = np.where(pad, np.iinfo(np.int64).min, d + 1).max(axis=1)
    a[pad], c[pad] = 0, 0
    b[pad], d[pad] = -1, -1
    a += np.where(pad, bmax[:, None], 0)
    b += np.where(pad, bmax[:, None], 0)
    c += np.where(pad, dmax[:, None], 0)
    d += np.where(pad, dmax[:, None], 0)

    NX = 2 * S
    xs = np.sort(np.concatenate([a, b + 1], axis=1), axis=1)  # (G, NX)
    ys = np.sort(np.concatenate([c, d + 1], axis=1), axis=1)
    # (the device sweep kernel, repro.kernels.sweep_grid, reproduces
    # everything from here to the hot mask on-device; _extract_runs is the
    # shared tail both paths finish through)
    # row-wise searchsorted in one call: bias each group's (small, < 2**31)
    # coordinates into a disjoint int64 band
    bias = np.arange(G, dtype=np.int64)[:, None] << 33
    xs_f, ys_f = (xs + bias).ravel(), (ys + bias).ravel()
    row0 = np.arange(G, dtype=np.int64)[:, None] * NX

    def rs(flat_sorted, probes):
        return np.searchsorted(flat_sorted,
                               (probes + bias).ravel()).reshape(G, S) - row0

    xi_a, xi_b = rs(xs_f, a), rs(xs_f, b + 1)
    yi_c, yi_d = rs(ys_f, c), rs(ys_f, d + 1)

    # one global bincount of the +-1 corner pulses (weight 0 on padding)
    STR = NX + 1
    cell0 = np.arange(G, dtype=np.int64)[:, None] * ((NX + 1) * STR)
    w = np.where(pad, 0.0, 1.0).ravel()
    ww = np.concatenate([w, w])
    flat = lambda xi, yi: (cell0 + xi * STR + yi).ravel()
    L = G * (NX + 1) * STR
    pos = np.concatenate([flat(xi_a, yi_c), flat(xi_b, yi_d)])
    neg = np.concatenate([flat(xi_a, yi_d), flat(xi_b, yi_c)])
    diff = (np.bincount(pos, weights=ww, minlength=L)
            - np.bincount(neg, weights=ww, minlength=L)
            ).reshape(G, NX + 1, STR).astype(np.int32)
    count = np.cumsum(np.cumsum(diff, axis=1), axis=2)
    hot = count[:, :NX - 1, :NX - 1] >= m
    hot &= (xs[:, 1:] > xs[:, :-1])[:, :, None]              # zero-width
    return _extract_runs(hot, xs, ys)


def _extract_runs(hot: np.ndarray, xs: np.ndarray, ys: np.ndarray
                  ) -> list[list[tuple[int, int, int, int]]]:
    """Maximal horizontal runs of the hot stripe mask, as per-group block
    lists — the shared tail of the host (``_sweep_small_batch``) and
    device (``repro.kernels.sweep_grid``) grouped sweeps.

    hot bool (G, NX-1, NX-1); xs/ys int (G, NX) sorted stripe boundaries
    (stripe i spans ``xs[i]..xs[i+1]-1``).  Vectorized: +1/-1 edges of the
    zero-padded hot mask mark run starts / one-past-run ends.
    """
    G, _, ny = hot.shape
    NX = ny + 1
    out: list[list[tuple[int, int, int, int]]] = [[] for _ in range(G)]
    if not hot.any():
        return out
    hpad = np.zeros((G, NX - 1, NX + 1), np.int8)
    hpad[:, :, 1:NX] = hot
    edges = np.diff(hpad, axis=2)
    gs, rows, cs = np.nonzero(edges == 1)     # run starts, row-major
    _, _, ce = np.nonzero(edges == -1)        # aligned exclusive run ends
    flat_blocks = np.stack([xs[gs, rows], xs[gs, rows + 1] - 1,
                            ys[gs, cs], ys[gs, ce] - 1], axis=1).tolist()
    grp = np.searchsorted(gs, np.arange(G + 1))   # gs ascending (row-major)
    for g in range(G):
        lo, hi = grp[g], grp[g + 1]
        if hi > lo:
            out[g] = [tuple(int(x) for x in r) for r in flat_blocks[lo:hi]]
    return out


_POS = 32                # bits of a position (a window bound, or one past)
_MASK = (1 << _POS) - 1
_SEG_MAX = 1 << 29      # (group, coordinate) ids that keep the keys in 63


def _coverage(lo: np.ndarray, hi: np.ndarray, seg: np.ndarray, per: int
              ) -> tuple[np.ndarray, np.ndarray]:
    """How many segments' unions of half-open intervals ``[lo, hi)``
    cover each position of their group (``seg // per``), as the sorted
    event keys ``(group << 32) | position`` and the count after each."""
    base = seg << _POS
    start, end = base | lo, base | hi
    if (start[1:] < start[:-1]).any():
        o = np.argsort(start, kind="stable")     # adaptive to sorted runs
        start, end = start[o], end[o]
    # pieces of each segment's union: an interval starts a new piece
    # where it begins past the furthest end before it in its segment
    reach = np.maximum.accumulate(end)
    first = np.flatnonzero(np.concatenate(
        [[True], start[1:] > reach[:-1]]))
    last = np.append(first[1:] - 1, len(start) - 1)

    def grouped(key):
        return ((key >> _POS) // per << _POS) | (key & _MASK)

    # ends (low bit 0) sort before starts at one position
    ev = np.sort(np.concatenate([grouped(reach[last]) << 1,
                                 grouped(start[first]) << 1 | 1]))
    return ev >> 1, np.cumsum((ev & 1) * 2 - 1)


def _hot_groups(cover: tuple[np.ndarray, np.ndarray], G: int, m: int
                ) -> np.ndarray:
    """bool (G,): the groups with a position ``_coverage`` counts >= m
    times."""
    pos, depth = cover
    out = np.zeros(G, bool)
    out[pos[depth >= m] >> _POS] = True
    return out


def _meets_hot(lo: np.ndarray, hi: np.ndarray, grp: np.ndarray,
               cover: tuple[np.ndarray, np.ndarray], m: int) -> np.ndarray:
    """Whether each interval ``[lo, hi)`` of group ``grp`` meets a
    position that ``_coverage`` counts at least m times."""
    pos, depth = cover
    hot = depth >= m
    # measure of the hot set before each event; a group's last event is
    # an end at count 0, so nothing hot spans two groups
    before = np.concatenate([[0], np.cumsum(
        np.where(hot[:-1], np.diff(pos), 0))])

    def hot_below(x):
        i = np.maximum(np.searchsorted(pos, x, side="right") - 1, 0)
        return before[i] + hot[i] * np.maximum(x - pos[i], 0)

    base = grp << _POS
    return hot_below(base | hi) > hot_below(base | lo)


def _large_groups_hot(rect: np.ndarray, cid: np.ndarray, sizes: np.ndarray,
                      m: int) -> np.ndarray:
    """Which of G groups may hold a cell covered by >= m of its
    rectangles: a bool (G,) mask that is False only where
    ``_sweep_text`` of the group returns ``[]``.

    rect: int (N, 4) rows (a, b, c, d) of the G groups back to back
    (``sizes[g]`` rows each), cid (N,) the sketch coordinate of each row.
    One coordinate's windows in one text are disjoint (a cell has one
    min-hash per coordinate), so a cell covered >= m times lies in the
    windows of >= m distinct coordinates.  Its row i then lies in the
    union of the x-projections ``[a, b+1)`` of >= m coordinates, its
    column j in the union of the y-projections ``[c, d+1)`` of >= m
    coordinates, and every rectangle covering it meets both hot sets.  A
    group whose rectangles meeting both hot sets span fewer than m
    coordinates holds no such cell.  The rectangles that meet both still
    hold every rectangle covering a hot cell, so the test repeats on them
    until it drops none.  Sorts and ``searchsorted`` over the rows, no
    grid.
    """
    G = len(sizes)
    if G == 0:
        return np.zeros(0, bool)
    per = int(cid.max()) + 1
    if G * per > _SEG_MAX:
        cut = _SEG_MAX // per
        n = int(sizes[:cut].sum())
        return np.concatenate([
            _large_groups_hot(rect[:n], cid[:n], sizes[:cut], m),
            _large_groups_hot(rect[n:], cid[n:], sizes[cut:], m)])
    grp = np.repeat(np.arange(G, dtype=np.int64), sizes)
    a, b, c, d = (rect[:, i].astype(np.int64) for i in range(4))
    # (group, segment, a, b, c, d) of the rows still in play
    rows = (grp, grp * per + cid, a, b, c, d)

    def take(mask):
        at = np.flatnonzero(mask)
        return tuple(v[at] for v in rows)

    while True:
        n = len(rows[0])
        x = _coverage(rows[2], rows[3] + 1, rows[1], per)
        rows = take(_hot_groups(x, G, m)[rows[0]])
        if not len(rows[0]):
            return np.zeros(G, bool)
        y = _coverage(rows[4], rows[5] + 1, rows[1], per)
        rows = take(_hot_groups(y, G, m)[rows[0]])
        grp, seg, a, b, c, d = rows
        meets = _meets_hot(a, b + 1, grp, x, m) & \
            _meets_hot(c, d + 1, grp, y, m)
        coords = np.bincount(seg[meets], minlength=G * per)
        keep = (coords.reshape(G, per) > 0).sum(axis=1) >= m
        rows = take(meets & keep[grp])
        if len(rows[0]) in (0, n):
            return keep


def _gather_coord(index, i: int, probe_keys: list
                  ) -> tuple[np.ndarray, np.ndarray]:
    """All windows colliding with the B probe keys on coordinate ``i``:
    (query ids (M,), windows (M, 5) int64)."""
    if index.is_frozen:
        table = index.frozen[i]
        packed = table.encode(probe_keys)
        starts, ends = table.probe(packed)
        counts = ends - starts
        qids = np.repeat(np.arange(len(probe_keys), dtype=np.int64), counts)
        rows = table.windows[_concat_ranges(starts, counts)]
        return qids, rows.astype(np.int64)
    qid_chunks, win_chunks = [], []
    for b, key in enumerate(probe_keys):
        wins = index.tables[i].get(key)
        if wins:
            qid_chunks.append(np.full(len(wins), b, np.int64))
            win_chunks.append(np.asarray(wins, np.int64))
    if not qid_chunks:
        return np.empty(0, np.int64), np.empty((0, 5), np.int64)
    return np.concatenate(qid_chunks), np.concatenate(win_chunks)


def _gather_arena(index, sketches, probe_backend: str
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One-shot probe of ALL B*k coordinates against the fused arena:
    (query ids (M,), windows (M, 5) int64, coordinate ids (M,))."""
    arena = index.arena()
    k = arena.k
    pkeys, coords, valid = arena.encode_batch(sketches)
    if probe_backend == "device":
        from .device_plan import resident_probe
        starts, ends = resident_probe(index, pkeys, coords, valid)
    else:
        starts, ends = arena.probe(
            pkeys, coords, valid,
            backend="pallas" if probe_backend == "pallas" else "numpy")
    counts = ends - starts
    rows = arena.windows[_concat_ranges(starts, counts)]
    probe_ids = np.repeat(np.arange(len(pkeys), dtype=np.int64), counts)
    return probe_ids // k, rows.astype(np.int64), probe_ids % k


def batch_query(index, queries, theta: float, *,
                options: QueryOptions | None = None,
                sketches=UNSET,
                sketch_backend=UNSET,
                probe_backend=UNSET,
                sweep=UNSET,
                stage_times: dict | None = None) -> list[list[Alignment]]:
    """Definition-1 alignment for a batch of queries (the serving path).

    Execution comes in as ``options=QueryOptions(...)``: the ``plan``
    field picks the pipeline (``"cpu"`` — exact host sketch, one host
    ``searchsorted`` over the fused arena, vectorized grouped sweep;
    ``"device"`` — arena resident on the accelerator, probe binary search
    and small-group sweep on the device, fused so only probe inputs go
    up and final block extents come down; ``"auto"`` — device when a real
    accelerator backs jax, else cpu), resolved ONCE per batch by
    :func:`repro.core.plan.resolve_plan`.  Stage fields on the options
    object pin individual stages for debugging.  All plans and pins are
    block-identical.

    ``QueryOptions.sketches`` short-circuits sketching when the caller
    already holds the batch's sketch coordinates (the sharded fan-out
    computes them once and reuses them on every shard).

    The bare ``sketches=``/``sketch_backend=``/``probe_backend=``/
    ``sweep=`` keywords are deprecated (one release behind a
    ``DeprecationWarning``); they coerce to pins on the cpu plan.

    ``stage_times``, when given, accumulates per-stage wall seconds under
    the keys ``"sketch"``, ``"probe"`` and ``"sweep"`` and their children
    (the names of :mod:`repro.core.spans`; the serve-path metrics hook;
    += so one dict can span many batches).
    """
    opts = coerce_query_options(options, "batch_query", sketches=sketches,
                                sketch_backend=sketch_backend,
                                probe_backend=probe_backend, sweep=sweep)
    xp = resolve_plan(opts)
    B = len(queries)
    if B == 0:
        return []
    m = max(1, math.ceil(index.scheme.k * theta))
    with span(stage_times, "sketch"):
        sk = opts.sketches
        if sk is None:
            sk = index.scheme.sketch_batch(queries,
                                           backend=xp.sketch_backend)
    if xp.fused and getattr(index, "is_frozen", False):
        from .device_plan import fused_batch_query
        return fused_batch_query(index, sk, B, m, stage_times=stage_times)
    with span(stage_times, "probe"):
        gathered = batch_probe(index, sk, probe_backend=xp.probe_backend)
    with span(stage_times, "sweep"):
        return _sweep_gathered(gathered, B, m, xp.sweep, stage_times)


def batch_probe(index, sketches, *, probe_backend: str = "numpy"
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The probe stage of ``batch_query``: all windows colliding with the
    batch's sketches, as (query ids (M,), windows (M, 5) int64, coordinate
    ids (M,)).

    Pure NumPy/mmap work that releases the GIL in searchsorted/gather —
    the sharded fan-out overlaps THIS stage across shards with a thread
    pool and keeps the (GIL-bound) sweep stage serial.
    """
    if getattr(index, "is_live", False):
        # live index: merge the frozen-arena and delta-dict probes (delta
        # tids re-based after the frozen corpus) into one gathered triple
        return index.batch_probe(sketches, probe_backend=probe_backend)
    B = len(sketches)
    k = index.scheme.k
    if index.is_frozen and probe_backend != "percoord":
        return _gather_arena(index, sketches, probe_backend)
    qid_chunks, win_chunks, cid_chunks = [], [], []
    for i in range(k):
        qids, wins = _gather_coord(index, i, [sketches[b][i]
                                              for b in range(B)])
        if len(qids):
            qid_chunks.append(qids)
            win_chunks.append(wins)
            cid_chunks.append(np.full(len(qids), i, np.int64))
    if not qid_chunks:
        return (np.empty(0, np.int64), np.empty((0, 5), np.int64),
                np.empty(0, np.int64))
    return (np.concatenate(qid_chunks), np.concatenate(win_chunks),
            np.concatenate(cid_chunks))


def _group_bounds(qid_all: np.ndarray, tid_all: np.ndarray,
                  cid_all: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(query, text) grouping of a gathered probe.

    Returns ``(order, starts, ends, distinct)``: ``order`` stably sorts the
    gathered rows by (query id, text id) — both gather orders
    (coordinate-major and query-major) are coordinate-ascending within a
    (query, text) group, which the stable sort preserves — ``starts``/
    ``ends`` bound each group in the sorted order, and ``distinct`` counts
    each group's distinct colliding sketch coordinates (the >= m
    prefilter, one reduceat).  Shared by the host dispatcher and the fused
    device pipeline (:mod:`repro.core.device_plan`).
    """
    order = np.lexsort((tid_all, qid_all))
    qid_s, tid_s, cid_s = qid_all[order], tid_all[order], cid_all[order]
    n = len(qid_s)
    change = (qid_s[1:] != qid_s[:-1]) | (tid_s[1:] != tid_s[:-1])
    bounds = np.flatnonzero(change) + 1
    starts = np.concatenate([[0], bounds])
    ends = np.concatenate([bounds, [n]])
    cid_step = np.empty(n, bool)
    cid_step[0] = True
    cid_step[1:] = cid_s[1:] != cid_s[:-1]
    cid_step[starts] = True
    distinct = np.add.reduceat(cid_step, starts)
    return order, starts, ends, distinct


#: small-group size buckets: padded width S stays tight for the (dominant)
#: tiny groups instead of paying the largest small group everywhere
_SIZE_BUCKETS = ((0, 8), (8, 16), (16, _SMALL_GROUP_MAX))


def _pad_groups(values: np.ndarray, starts: np.ndarray, sizes: np.ndarray
                ) -> np.ndarray:
    """(G, S, ...) grid of the G groups ``values[starts[g]:starts[g] +
    sizes[g]]``, zero past each group's size (S is the largest size)."""
    G, S = len(sizes), int(sizes.max())
    out = np.zeros((G, S) + values.shape[1:], values.dtype)
    rows = values[_concat_ranges(starts, sizes)]
    slot = np.arange(len(rows)) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    out[np.repeat(np.arange(G), sizes), slot] = rows
    return out


def _emit(kept: np.ndarray, blocks: dict, starts: np.ndarray,
          qid_s: np.ndarray, tid_s: np.ndarray, distinct: np.ndarray,
          B: int) -> list[list[Alignment]]:
    """Each query's alignments: the kept groups (ascending, so in text-id
    order) whose sweep found blocks."""
    results: list[list[Alignment]] = [[] for _ in range(B)]
    for g in kept.tolist():
        if blocks[g]:
            lo = starts[g]
            results[int(qid_s[lo])].append(
                Alignment(text_id=int(tid_s[lo]), blocks=blocks[g],
                          ncoords=int(distinct[g])))
    return results


def _sweep_large(large: np.ndarray, rect: np.ndarray, cid: np.ndarray,
                 sizes: np.ndarray, m: int, blocks: dict,
                 times: dict | None) -> int:
    """``blocks[g]`` for the large groups: ``[]`` for those
    ``_large_groups_hot`` rejects (timed as ``sweep.large.filter``),
    ``_sweep_text`` of all the rows of the rest.  rect and cid hold the
    groups' rows back to back.  Returns the number rejected."""
    t = time.perf_counter()
    keep = _large_groups_hot(rect, cid, sizes[large], m)
    add_seconds(times, "sweep.large.filter", time.perf_counter() - t)
    ends = np.cumsum(sizes[large]).tolist()
    for g, kept, hi, n in zip(large.tolist(), keep.tolist(), ends,
                              sizes[large].tolist()):
        blocks[g] = _sweep_text(rect[hi - n:hi], m) if kept else []
    return len(large) - int(keep.sum())


def _sweep_gathered(gathered, B: int, m: int, sweep: str,
                    times: dict | None = None) -> list[list[Alignment]]:
    """Group the gathered windows by (query, text) and plane-sweep each
    group (the second stage of ``batch_query``); ``times`` accumulates
    the ``sweep.*`` spans."""
    qid_all, win_all, cid_all = gathered
    if not len(qid_all):
        return [[] for _ in range(B)]

    with span(times, "sweep.group"):
        order, starts, ends, distinct = _group_bounds(
            qid_all, win_all[:, 0], cid_all)
        qid_all, win_all = qid_all[order], win_all[order]
        sizes = ends - starts
        kept = np.flatnonzero(distinct >= m)
        is_small = (sizes[kept] <= _SMALL_GROUP_MAX) & \
            (sweep in ("grouped", "device"))
        small, large = kept[is_small], kept[~is_small]

    blocks: dict[int, list] = {}
    grids = []                  # (group ids, hot, xs, ys) per size bucket
    for b_lo, b_hi in _SIZE_BUCKETS:
        ids = small[(sizes[small] > b_lo) & (sizes[small] <= b_hi)]
        if not len(ids):
            continue
        arr = _pad_groups(win_all[:, 1:5], starts[ids], sizes[ids])
        if sweep == "device":
            from ..kernels.sweep_grid import sweep_small_batch_device
            with span(times, "sweep.device"):
                grids.append((ids, *sweep_small_batch_device(
                    arr, sizes[ids], m)))
        else:
            blocks.update(zip(ids.tolist(),
                              _sweep_small_batch(arr, sizes[ids], m)))

    with span(times, "sweep.large"):
        at = _concat_ranges(starts[large], sizes[large])
        rejected = _sweep_large(large, win_all[at, 1:5], cid_all[order[at]],
                                sizes, m, blocks, times)
    if sweep == "device":
        from .device_plan import add_counts
        add_counts(sweep_launches=len(grids), probe_windows=len(qid_all),
                   groups_kept=len(kept), host_large_groups=len(large),
                   host_large_windows=sizes[large].sum(),
                   host_large_rejected=rejected)

    with span(times, "sweep.emit"):
        for ids, hot, xs, ys in grids:
            blocks.update(zip(ids.tolist(), _extract_runs(hot, xs, ys)))
        return _emit(kept, blocks, starts, qid_all, win_all[:, 0], distinct,
                     B)


def estimate_similarity(index, query_tokens, data_tokens
                        ) -> float:
    """Sketch-estimated Jaccard between two full texts (Eq. 2 / Eq. 5):
    one vectorized equality over the k sketch coordinates."""
    sq = index.scheme.sketch(query_tokens)
    sd = index.scheme.sketch(data_tokens)
    if sq and isinstance(sq[0], (tuple, list)):
        # ICWS identities: exact (token, k_int) pairs -> (k, 2) int64
        eq = np.asarray(sq, np.int64) == np.asarray(sd, np.int64)
        return float(np.mean(eq.all(axis=1)))
    # multiset identities: 61/64-bit hashes -> uint64 (the frozen tables'
    # key packing)
    return float(np.mean(np.array(sq, np.uint64) == np.array(sd, np.uint64)))
