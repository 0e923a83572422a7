"""The fused device path groups probe hits by text runs.

Inside each arena slot's extent, the windows of one text lie in runs.
The device plan's run grouping (``device_plan._kept_groups``) counts each
(query, text) group's distinct coordinates over those runs and expands
the rows of the kept groups alone.  The contract under test: it keeps
exactly the rows the cpu plan's row-level grouping (``query._group_bounds``,
``query._row_groups``) keeps, in the same order and as the same
``KeptGroups`` record, and answers block for block as ``plan="cpu"``
does, on coord and packed arenas, compacted live stores and arenas whose
extents hold one text in several runs.
"""

import numpy as np
import pytest

from repro.core import (IndexBuilder, LiveIndex, MultisetScheme,
                        QueryOptions, batch_query, make_scheme, save_index)
from repro.core import device_plan as dp
from repro.core.frozen import MODE_COORD, MODE_PACKED, _concat_ranges
from repro.core.query import _SMALL_GROUP_MAX, _group_bounds, batch_probe
from repro.core.query import _row_groups as row_grouping

K = 8
SCHEMES = {
    MODE_COORD: lambda docs: MultisetScheme(seed=13, k=K),
    MODE_PACKED: lambda docs: make_scheme("tfidf", seed=5, k=K,
                                          corpus=docs),
}


def _corpus(rng, n_docs=6, vocab=30, n=120):
    docs = [rng.integers(0, vocab, size=n).astype(np.int64)
            for _ in range(n_docs)]
    docs[-1] = docs[1].copy()                     # planted duplicate
    return docs


def _queries(rng, docs):
    qs = [d[:100].copy() for d in docs[:4]]
    qs += [docs[i][5:30].copy() for i in range(len(docs))]
    qs.append(rng.integers(1000, 1030, size=12).astype(np.int64))  # miss
    return qs


def _frozen(mode, docs):
    frozen = IndexBuilder(scheme=SCHEMES[mode](docs)).build(docs).freeze()
    assert frozen.arena().mode == mode
    return frozen


def _blocks(res):
    return [[(a.text_id, a.blocks, a.ncoords) for a in r] for r in res]


def _extents(index, qs):
    arena = index.arena()
    pk, co, va = arena.encode_batch(index.scheme.sketch_batch(qs))
    return arena.probe(pk, co, va)


def _row_groups(index, starts, ends, m):
    """The cpu plan's grouping of the probe's rows: the kept groups'
    (rows, coordinate ids, sizes, query ids, text ids, distinct)."""
    k = index.arena().k
    counts = ends - starts
    rows = _concat_ranges(starts, counts)
    probe = np.repeat(np.arange(len(starts)), counts)
    q, c = probe // k, probe % k
    tid = np.asarray(index.arena().windows)[rows, 0]
    order, lo, hi, distinct = _group_bounds(q, tid, c)
    kept = np.flatnonzero(distinct >= m)
    sizes = (hi - lo)[kept]
    at = order[_concat_ranges(lo[kept], sizes)]
    head = order[lo[kept]]
    return rows[at], c[at], sizes, q[head], tid[head], distinct[kept]


FIELDS = ("rows", "cids", "sizes", "qids", "tids", "distinct")


def _kept(index, starts, ends, m):
    da = dp.device_arena(index)
    probe_r, run_r = dp._probe_runs(da.run_start, starts, ends)
    return dp._kept_groups(da, probe_r, run_r, index.arena().k, m,
                           hits=int((ends - starts).sum()))


def _run_groups(index, starts, ends, m):
    g = _kept(index, starts, ends, m)
    return tuple(getattr(g, name) for name in FIELDS)


def _runs_in(index, starts, ends):
    """Maximal stretches of one text id inside each extent, counted row
    by row."""
    tid = np.asarray(index.arena().windows)[:, 0]
    return sum(int(np.count_nonzero(np.diff(tid[s:e]))) + 1
               for s, e in zip(starts.tolist(), ends.tolist()) if e > s)


def _assert_same_groups(index, qs, theta):
    m = int(np.ceil(K * theta))
    starts, ends = _extents(index, qs)
    want = _row_groups(index, starts, ends, m)
    kept = _kept(index, starts, ends, m)
    got = tuple(getattr(kept, name) for name in FIELDS)
    for name, w, g in zip(FIELDS, want, got):
        assert np.array_equal(g, w), name
    assert got[0].dtype == got[1].dtype == np.int32
    # the engine's row grouping returns the same record over the rows it
    # gathered on the host: the same rectangles, coordinates and groups
    rows = row_grouping(*batch_probe(index, index.scheme.sketch_batch(qs)),
                        m)
    assert np.array_equal(rows.source.read(rows.rows),
                          kept.source.read(kept.rows))
    for name in FIELDS[1:]:
        assert np.array_equal(getattr(rows, name), getattr(kept, name)), \
            name
    assert rows.hits == kept.hits
    return got


def _shuffle_extents(index, seed):
    """The index with each arena extent's windows permuted, so a text's
    windows lie in several runs of one extent (a store whose extents are
    not in text order)."""
    arena = index.arena()
    rng = np.random.default_rng(seed)
    off = np.asarray(arena.offsets)
    slot = np.repeat(np.arange(len(off) - 1), np.diff(off))
    perm = np.lexsort((rng.random(len(slot)), slot))
    arena.windows = np.ascontiguousarray(np.asarray(arena.windows)[perm])
    index._device_arena = None
    return index


# --------------------------------------------------------------------------
# the run table
# --------------------------------------------------------------------------

def test_text_runs_cut_at_text_changes_and_extent_bounds():
    tid = np.array([0, 0, 1, 0, 0, 0, 2, 2, 3], np.int32)
    offsets = np.array([0, 5, 8, 9], np.int32)
    run_start, run_tid = dp._text_runs(tid, offsets)
    assert run_start.tolist() == [0, 2, 3, 5, 6, 8, 9]
    assert run_tid.tolist() == [0, 1, 0, 0, 2, 3]
    assert run_start.dtype == np.int32


@pytest.mark.parametrize("offsets", [[0], [0, 0]])
def test_text_runs_of_an_empty_arena(offsets):
    run_start, run_tid = dp._text_runs(np.zeros(0, np.int32),
                                       np.array(offsets, np.int32))
    assert run_start.tolist() == [0] and run_tid.size == 0


def test_run_table_is_cached_with_the_resident_arena():
    rng = np.random.default_rng(0)
    frozen = _frozen(MODE_COORD, _corpus(rng))
    da = dp.device_arena(frozen)
    tid = np.asarray(frozen.arena().windows)[:, 0]
    assert da.run_start[-1] == len(tid)
    assert np.array_equal(np.repeat(da.run_tid, np.diff(da.run_start)), tid)
    assert dp.device_arena(frozen).run_start is da.run_start


# --------------------------------------------------------------------------
# the run grouping keeps the row grouping's rows, in its order
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [MODE_COORD, MODE_PACKED])
@pytest.mark.parametrize("shuffled", [False, True])
@pytest.mark.parametrize("theta", [0.3, 0.5, 1.0])
def test_run_grouping_keeps_the_row_groupings_rows(mode, shuffled, theta):
    rng = np.random.default_rng(1)
    docs = _corpus(rng)
    index = _frozen(mode, docs)
    qs = _queries(rng, docs)
    cpu = batch_query(index, qs, theta, options=QueryOptions(plan="cpu"))
    if shuffled:
        index = _shuffle_extents(index, seed=2)
        starts, ends = _extents(index, qs)
        # one text in several runs of one extent: more runs than the
        # extents hold (extent, text) pairs
        assert _runs_in(index, starts, ends) > len(
            {(s, t) for s, e in zip(starts, ends) for t in
             np.asarray(index.arena().windows)[s:e, 0].tolist()})
    _assert_same_groups(index, qs, theta)
    dev = batch_query(index, qs, theta, options=QueryOptions(plan="device"))
    assert _blocks(dev) == _blocks(cpu)
    assert _blocks(batch_query(index, qs, theta, options=QueryOptions(
        plan="cpu"))) == _blocks(cpu)


@pytest.mark.parametrize("mode", [MODE_COORD, MODE_PACKED])
def test_small_and_large_groups_in_one_batch(mode):
    rng = np.random.default_rng(10)
    docs = _corpus(rng)
    index = _frozen(mode, docs)
    qs = _queries(rng, docs)
    sizes = _assert_same_groups(index, qs, 0.5)[2]
    assert (sizes <= _SMALL_GROUP_MAX).any()
    assert (sizes > _SMALL_GROUP_MAX).any()
    opts = QueryOptions(plan="device")
    dp.reset_transfer_stats()
    dev = batch_query(index, qs, 0.5, options=opts)
    st = dp.transfer_stats()
    assert st["groups_kept"] == len(sizes)
    assert st["host_large_groups"] == int((sizes > _SMALL_GROUP_MAX).sum())
    assert st["sweep_launches"] >= 1
    assert _blocks(dev) == _blocks(
        batch_query(index, qs, 0.5, options=QueryOptions(plan="cpu")))


def test_compacted_live_store(tmp_path):
    rng = np.random.default_rng(4)
    base = _corpus(rng, n_docs=8)
    scheme = MultisetScheme(seed=13, k=K)
    save_index(IndexBuilder(scheme=scheme).build(base).freeze(),
               tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    extra = [base[2].copy(), base[0][::-1].copy(),
             rng.integers(0, 30, 120).astype(np.int64)]
    for t in extra:
        live.add_text(t)
    live.compact()
    assert live.delta.num_texts == 0
    qs = _queries(rng, base + extra)
    for theta in (0.5, 0.75):
        _assert_same_groups(live.frozen, qs, theta)
        oracle = IndexBuilder(scheme=scheme).build(base + extra)
        assert _blocks(live.batch_query(
            qs, theta, options=QueryOptions(plan="device"))) == \
            _blocks(batch_query(oracle, qs, theta))


# --------------------------------------------------------------------------
# nothing to group, nothing kept, and the counters
# --------------------------------------------------------------------------

def test_a_batch_that_hits_nothing():
    rng = np.random.default_rng(5)
    index = _frozen(MODE_COORD, _corpus(rng))
    qs = [rng.integers(1000, 1030, size=12).astype(np.int64)
          for _ in range(3)]
    starts, ends = _extents(index, qs)
    assert not (ends - starts).any()
    got = _run_groups(index, starts, ends, 4)
    assert all(len(a) == 0 for a in got)
    dp.reset_transfer_stats()
    res = batch_query(index, qs, 0.5, options=QueryOptions(plan="device"))
    assert res == [[], [], []]
    st = dp.transfer_stats()
    assert st["probe_windows"] == st["probe_text_runs"] == 0
    assert st["groups_kept"] == 0


def test_a_batch_whose_groups_are_all_dropped():
    rng = np.random.default_rng(6)
    docs = _corpus(rng)
    index = _frozen(MODE_COORD, docs)
    # half of each query's tokens are in no text, so few coordinates
    # collide and none of its groups reaches all k
    qs = [np.concatenate([d[:20], rng.integers(1000, 1100, 20)])
          for d in docs[:3]]
    starts, ends = _extents(index, qs)
    got = _assert_same_groups(index, qs, 1.0)
    assert (ends - starts).any() and len(got[2]) == 0
    dp.reset_transfer_stats()
    res = batch_query(index, qs, 1.0, options=QueryOptions(plan="device"))
    assert res == [[], [], []]
    st = dp.transfer_stats()
    assert st["probe_text_runs"] > 0 and st["groups_kept"] == 0


@pytest.mark.parametrize("mode", [MODE_COORD, MODE_PACKED])
@pytest.mark.parametrize("shuffled", [False, True])
def test_probe_windows_and_text_runs_count_the_extents(mode, shuffled):
    rng = np.random.default_rng(7)
    docs = _corpus(rng)
    index = _frozen(mode, docs)
    if shuffled:
        index = _shuffle_extents(index, seed=3)
    qs = _queries(rng, docs)
    starts, ends = _extents(index, qs)
    dp.reset_transfer_stats()
    batch_query(index, qs, 0.5, options=QueryOptions(plan="device"))
    st = dp.transfer_stats()
    # every window the probe hit, not only the rows of the kept groups
    assert st["probe_windows"] == int((ends - starts).sum())
    assert st["probe_text_runs"] == _runs_in(index, starts, ends)
    assert st["probe_windows"] > st["probe_text_runs"] > 0
