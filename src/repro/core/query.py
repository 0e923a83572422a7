"""Query processing (Algorithm 2): sketch the query, probe the k inverted
lists, plane-sweep the collided compact windows for cells covered >= ⌈kθ⌉
times (those subsequences have estimated Jaccard >= θ, Eq. 2/Eq. 5).

Two execution paths over the same algorithm:

* ``query``       — one query at a time; works on mutable (dict) and frozen
  indexes alike.
* ``batch_query`` — the serving path, one pipeline for every store kind
  (:func:`run_batch`): sketch the whole batch at once, probe each store
  level as :func:`_probe_level` chooses from the plan and the level's
  kind, group the collided windows by (query, text) into
  :class:`KeptGroups`, and sweep them with :func:`sweep_groups`: the many
  tiny groups of Zipf traffic a size bucket at a time through one
  vectorized (or device) small-group sweep, the large groups through an
  exact test and the per-group ``_sweep_text``.  Every plan returns
  block-for-block the same results as looping ``query``.
"""

from __future__ import annotations

import math
import time
from collections import defaultdict
from dataclasses import dataclass
from functools import partial

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


def batch_probe(index, sketches
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The host probe of one store level: all windows colliding with the
    batch's sketches, as (query ids (M,), windows (M, 5) int64, coordinate
    ids (M,)).  A frozen level probes its fused arena in ONE
    ``searchsorted`` and one gather (NumPy/mmap work that releases the
    GIL, which the sharded fan-out overlaps); a mutable level probes its
    dict tables coordinate by coordinate.
    """
    if index.is_frozen:
        arena = index.arena()
        k = arena.k
        pkeys, coords, valid = arena.encode_batch(sketches)
        starts, ends = arena.probe(pkeys, coords, valid)
        counts = ends - starts
        rows = arena.windows[_concat_ranges(starts, counts)]
        probe_ids = np.repeat(np.arange(len(pkeys), dtype=np.int64), counts)
        return probe_ids // k, rows.astype(np.int64), probe_ids % k
    qid, win, cid = [], [], []
    for i in range(index.scheme.k):
        for b, sk in enumerate(sketches):
            wins = index.tables[i].get(sk[i])
            if wins:
                qid += [b] * len(wins)
                cid += [i] * len(wins)
                win += wins
    return (np.array(qid, np.int64), np.array(win, np.int64).reshape(-1, 5),
            np.array(cid, np.int64))


# --------------------------------------------------------------------------
# the batch pipeline: sketch, probe each store level, sweep its kept groups
# --------------------------------------------------------------------------


@dataclass
class KeptGroups:
    """The (query, text) groups of one store level with at least ⌈kθ⌉
    distinct colliding coordinates, in (query, text) order — what both
    groupings return: the row grouping (:func:`_row_groups`) of rows
    gathered on the host, and the run grouping
    (``device_plan._kept_groups``) of a level whose arena is resident.

    ``rows`` and ``cids`` hold each kept group's row ids and their
    coordinate ids back to back, coordinate-ascending within a group;
    ``sizes``, ``qids``, ``tids`` and ``distinct`` are per group.
    ``source`` knows where a row's rectangle lives: :class:`HostRows`, or
    the level's ``device_plan.DeviceArena``.  ``hits`` counts the windows
    the probe hit and ``runs`` their text runs (the run grouping's input;
    0 for the row grouping).
    """

    rows: np.ndarray
    cids: np.ndarray
    sizes: np.ndarray
    qids: np.ndarray
    tids: np.ndarray
    distinct: np.ndarray
    source: object
    hits: int
    runs: int = 0


class HostRows:
    """The row source of a level gathered on the host: its (a, b, c, d)
    rectangle rows in memory."""

    reads_mmap = False

    def __init__(self, rect: np.ndarray):
        self.rect = rect

    def read(self, rows: np.ndarray) -> np.ndarray:
        return self.rect[rows]

    def device_rects(self, idx: np.ndarray) -> tuple[np.ndarray, int]:
        """The rows of the index grid, to upload; and their bytes."""
        rect = self.rect[idx].astype(np.int32)
        return rect, rect.nbytes


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
    prefilter, one reduceat).
    """
    order = np.lexsort((tid_all, qid_all))
    qid_s, tid_s, cid_s = qid_all[order], tid_all[order], cid_all[order]
    n = len(qid_s)
    step = np.ones(n, bool)
    step[1:] = (qid_s[1:] != qid_s[:-1]) | (tid_s[1:] != tid_s[:-1])
    starts = np.flatnonzero(step)
    ends = starts + np.diff(np.append(starts, n))
    step[1:] |= cid_s[1:] != cid_s[:-1]
    distinct = np.add.reduceat(step, starts)
    return order, starts, ends, distinct


def _row_groups(qid: np.ndarray, win: np.ndarray, cid: np.ndarray, m: int
                ) -> KeptGroups:
    """The row grouping: the kept groups of windows gathered on the host
    (``batch_probe``'s triple)."""
    order, starts, ends, distinct = _group_bounds(qid, win[:, 0], cid)
    sizes = ends - starts
    kept = np.flatnonzero(distinct >= m)
    rows = order[_concat_ranges(starts[kept], sizes[kept])]
    head = order[starts[kept]]
    return KeptGroups(rows=rows, cids=cid[rows], sizes=sizes[kept],
                      qids=qid[head], tids=win[head, 0],
                      distinct=distinct[kept], source=HostRows(win[:, 1:5]),
                      hits=len(qid))


class _HostProbe:
    """A level probed on the host: the windows it hit, already gathered;
    grouped row by row."""

    def __init__(self, gathered):
        self.gathered = gathered

    def gather(self, times: dict | None) -> None:
        pass                                 # batch_probe gathered them

    def kept(self, m: int) -> KeptGroups:
        return _row_groups(*self.gathered, m)


def _probe_level(level, sketches, device: bool, times: dict | None):
    """Probe one store level: the one place that chooses, from the plan
    and the level's kind, how a level is probed and grouped.

    * device plan, frozen level: the resident-arena probe, grouped over
      the text runs of its extents (``device_plan.ResidentProbe``);
    * cpu plan, frozen level: one host ``searchsorted`` of the arena, the
      hit rows gathered and grouped row by row;
    * mutable (dict) level, either plan: the per-coordinate dict probe,
      grouped row by row.

    Returns the probed level: ``gather(times)`` finishes the probe stage
    on the calling thread, ``kept(m)`` groups it (:class:`KeptGroups`).
    """
    if device and level.is_frozen:
        from .device_plan import ResidentProbe
        return ResidentProbe(level, sketches, times)
    return _HostProbe(batch_probe(level, sketches))


def _shifted(ids, base: int):
    """The text-id map of a level whose ids start at ``base`` in a store
    whose ids ``ids`` maps (``None``: the identity)."""
    if not base:
        return ids
    if ids is None:
        return lambda t: t + base
    return lambda t: ids(t + base)


def probe_store(store, sketches, device: bool, times: dict | None = None,
                ids=None) -> list:
    """The probe stage of one store: each of its non-empty levels (one for
    a plain index, ``LiveIndex.query_levels`` for a live one) probed
    through :func:`_probe_level`, each paired with the map from its text
    ids to the ids the answers carry (``ids`` maps the store's own;
    ``None`` keeps them)."""
    levels = (store.query_levels() if getattr(store, "is_live", False)
              else ((store, 0),))
    return [(_probe_level(lv, sketches, device, times), _shifted(ids, base))
            for lv, base in levels]


def batch_query(index, queries, theta: float, *,
                options: QueryOptions | None = None,
                sketches=UNSET,
                sketch_backend=UNSET,
                stage_times: dict | None = None) -> list[list[Alignment]]:
    """Definition-1 alignment for a batch of queries (the serving path).

    Execution comes in as ``options=QueryOptions(...)``: the ``plan``
    field picks the pipeline (``"cpu"`` — exact host sketch, one host
    ``searchsorted`` over the fused arena, vectorized grouped sweep;
    ``"device"`` — arena resident on the accelerator, probe binary search
    and small-group sweep on the device, so only probe inputs go up and
    final block extents come down; ``"auto"`` — device when a real
    accelerator backs jax, else cpu), resolved ONCE per batch by
    :func:`repro.core.plan.resolve_plan`.  Both plans are block-identical.

    ``QueryOptions.sketches`` short-circuits sketching when the caller
    already holds the batch's sketch coordinates.

    The bare ``sketches=``/``sketch_backend=`` keywords are deprecated
    (one release behind a ``DeprecationWarning``).

    ``stage_times``, when given, accumulates per-stage wall seconds under
    the keys ``"sketch"``, ``"probe"`` and ``"sweep"`` and their children
    (the names of :mod:`repro.core.spans`; the serve-path metrics hook;
    += so one dict can span many batches).
    """
    opts = coerce_query_options(options, "batch_query", sketches=sketches,
                                sketch_backend=sketch_backend)
    return run_batch(index, queries, theta, opts, stage_times)


def run_batch(store, queries, theta: float, opts: QueryOptions,
              times: dict | None = None, *, ids=None,
              fan_out=None) -> list[list[Alignment]]:
    """The one batch pipeline behind every store kind's ``batch_query``:
    sketch the batch once, probe every level (:func:`probe_store`), then
    group and sweep the levels one after another (:func:`sweep_groups`).

    A (query, text) group never spans two levels or two shards, so
    sweeping level by level gives the blocks one sweep of their union
    would.  ``ids`` maps the store's text ids to the ids the answers carry
    (a live store's ``doc_map``).  ``fan_out``, when given, runs the probe
    stage over the shards of a sharded store: it is called with
    ``probe_store`` (the batch bound) and the plan's ``fanout`` and
    returns the probed levels of every shard.  With more than one level,
    or a map, each query's alignments are sorted by their text id.
    """
    xp = resolve_plan(opts)
    B = len(queries)
    if B == 0:
        return []
    m = max(1, math.ceil(store.scheme.k * theta))
    with span(times, "sketch"):
        sk = opts.sketches
        if sk is None:
            sk = store.scheme.sketch_batch(queries, backend=xp.sketch_backend)
    with span(times, "probe"):
        if fan_out is None:
            levels = probe_store(store, sk, xp.device, times, ids)
        else:
            levels = fan_out(partial(probe_store, sketches=sk,
                                     device=xp.device), xp.fanout)
        for probed, _ in levels:
            probed.gather(times)
    with span(times, "sweep"):
        swept = []
        for probed, level_ids in levels:
            with span(times, "sweep.group"):
                kept = probed.kept(m)
            swept.append(sweep_groups(kept, B, m, xp.device, times,
                                      level_ids))
        if len(levels) == 1 and levels[0][1] is None:
            return swept[0]
        return [sorted((al for res in swept for al in res[q]),
                       key=lambda a: a.text_id) for q in range(B)]


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


def _emit(blocks: dict, g: KeptGroups, B: int, ids
          ) -> list[list[Alignment]]:
    """Each query's alignments: the kept groups (in (query, text) order,
    so each query's in text-id order) whose sweep found blocks, their text
    ids mapped through ``ids`` when given."""
    results: list[list[Alignment]] = [[] for _ in range(B)]
    for i, (q, t, n) in enumerate(zip(g.qids.tolist(), g.tids.tolist(),
                                      g.distinct.tolist())):
        if blocks[i]:
            results[q].append(Alignment(
                text_id=t if ids is None else ids(t), blocks=blocks[i],
                ncoords=n))
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


def sweep_groups(g: KeptGroups, B: int, m: int, device: bool,
                 times: dict | None = None, ids=None
                 ) -> list[list[Alignment]]:
    """The sweep stage of every path, over one level's kept groups: each
    query's alignments (text ids mapped through ``ids`` when given).

    Groups of at most ``_SMALL_GROUP_MAX`` windows are swept a size bucket
    at a time: on the device plan by the device sweep
    (``kernels.sweep_grid``, span ``sweep.device``) over rectangles the
    row source puts on the device (a gather of the resident rectangle
    columns, or an upload of host rows), on the cpu plan by
    ``_sweep_small_batch``.  Larger groups are read from the row source
    (off the mmap for a resident level, timed as ``sweep.large.read``),
    tested by ``_large_groups_hot`` and swept on the host.  The work
    counters of ``device_plan.transfer_stats`` are counted here.
    """
    from .device_plan import add_counts
    sizes = g.sizes
    starts = np.cumsum(sizes) - sizes
    small = np.flatnonzero(sizes <= _SMALL_GROUP_MAX)
    large = np.flatnonzero(sizes > _SMALL_GROUP_MAX)

    blocks: dict[int, list] = {}
    grids = []                  # (group ids, hot, xs, ys) per size bucket
    up = down = 0               # device sweep bytes, up and down
    for b_lo, b_hi in _SIZE_BUCKETS:
        at = small[(sizes[small] > b_lo) & (sizes[small] <= b_hi)]
        if not len(at):
            continue
        idx = _pad_groups(g.rows, starts[at], sizes[at])
        if not device:
            blocks.update(zip(at.tolist(), _sweep_small_batch(
                g.source.read(idx), sizes[at], m)))
            continue
        from ..kernels.sweep_grid import sweep_small_batch_device
        sz32 = sizes[at].astype(np.int32)
        with span(times, "sweep.device"):
            rects, nbytes = g.source.device_rects(idx)
            hot, xs, ys = sweep_small_batch_device(rects, sz32, m)
        up += nbytes + sz32.nbytes
        down += hot.size + 2 * xs.size * 4       # b8 grid, i32 boundaries
        grids.append((at, hot, xs, ys))

    with span(times, "sweep.large"):
        t = time.perf_counter()
        at = _concat_ranges(starts[large], sizes[large])
        rect = g.source.read(g.rows[at])
        if g.source.reads_mmap:
            add_seconds(times, "sweep.large.read", time.perf_counter() - t)
        rejected = _sweep_large(large, rect, g.cids[at], sizes, m, blocks,
                                times)
    add_counts(probe_windows=g.hits, probe_text_runs=g.runs,
               groups_kept=len(sizes), host_large_groups=len(large),
               host_large_windows=sizes[large].sum(),
               host_large_rejected=rejected, sweep_launches=len(grids),
               h2d_bytes=up, d2h_bytes=down)

    with span(times, "sweep.emit"):
        for at, hot, xs, ys in grids:
            blocks.update(zip(at.tolist(), _extract_runs(hot, xs, ys)))
        return _emit(blocks, g, B, ids)


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
