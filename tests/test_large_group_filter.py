"""The exact test in front of the host sweep of large groups
(``repro.core.query._large_groups_hot``): it never rejects a group that
holds a cell covered >= m times, it keeps groups it cannot decide, and
the batch paths that call it stay block-identical to looping ``query``.
"""

import importlib

import numpy as np
import pytest

from repro.core import (IndexBuilder, LiveIndex, MultisetScheme,
                        QueryOptions, batch_query, make_scheme, query,
                        save_index)
from repro.core.device_plan import reset_transfer_stats, transfer_stats
from repro.core.frozen import _concat_ranges

Q = importlib.import_module("repro.core.query")

SCHEMES = {
    "multiset": lambda docs: MultisetScheme(seed=13, k=8),
    "tfidf": lambda docs: make_scheme("tfidf", seed=5, k=8, corpus=docs),
}


def _corpus(seed):
    # Zipf text over a small vocabulary: (query, text) groups of far more
    # than 32 windows; three queries are spans of documents (groups with
    # blocks), five are fresh text (groups that mostly hold none at 0.8)
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, 301) ** 1.1
    p /= p.sum()
    docs = [rng.choice(300, size=600, p=p) for _ in range(6)]
    qs = [docs[i][50:110].copy() for i in range(3)]
    qs += [rng.choice(300, size=60, p=p) for _ in range(5)]
    return docs, qs


def _large_groups(index, qs, m):
    """Rows (a, b, c, d), coordinates and sizes of the batch's kept groups
    of more than 32 windows, in the grouping's order."""
    q, w, c = Q.batch_probe(index, index.scheme.sketch_batch(qs))
    order, starts, ends, distinct = Q._group_bounds(q, w[:, 0], c)
    sizes = ends - starts
    large = np.flatnonzero((distinct >= m) & (sizes > Q._SMALL_GROUP_MAX))
    at = order[_concat_ranges(starts[large], sizes[large])]
    return w[at, 1:5], c[at], sizes[large]


def _groups(rect, sizes):
    ends = np.cumsum(sizes)
    return [rect[e - n:e] for e, n in zip(ends, sizes)]


# --------------------------------------------------------------------------
# (a) sound on every large group of small indexes
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("theta", [0.5, 0.8])
@pytest.mark.parametrize("kind", list(SCHEMES))
def test_never_rejects_a_group_the_sweep_finds_blocks_in(kind, theta, seed):
    docs, qs = _corpus(seed)
    index = IndexBuilder(scheme=SCHEMES[kind](docs)).build(docs).freeze()
    m = int(np.ceil(8 * theta))
    rect, cid, sizes = _large_groups(index, qs, m)
    assert len(sizes) >= 20
    keep = Q._large_groups_hot(rect, cid, sizes, m)
    found = np.array([bool(Q._sweep_text(g, m))
                      for g in _groups(rect, sizes)])
    assert found.any()
    assert not (found & ~keep).any()
    if theta == 0.8:
        assert (~keep).sum() >= len(sizes) // 2     # it does reject


@pytest.mark.parametrize("seg_max", [16, 100])
def test_groups_split_into_chunks_give_the_same_answer(seg_max,
                                                       monkeypatch):
    # a batch with more (group, coordinate) ids than the keys hold is
    # tested in chunks of whole groups
    docs, qs = _corpus(0)
    index = IndexBuilder(scheme=SCHEMES["multiset"](docs)).build(
        docs).freeze()
    rect, cid, sizes = _large_groups(index, qs, 7)
    whole = Q._large_groups_hot(rect, cid, sizes, 7)
    monkeypatch.setattr(Q, "_SEG_MAX", seg_max)
    assert Q._large_groups_hot(rect, cid, sizes, 7).tolist() == \
        whole.tolist()


# --------------------------------------------------------------------------
# (b), (c) hand-built groups
# --------------------------------------------------------------------------

def _shift(rects, scale, offset):
    r = np.asarray(rects, np.int64)
    # scale cells to scale x scale squares: (a, b) -> (s*a, s*b + s - 1)
    r = r * scale + np.array([0, scale - 1, 0, scale - 1])
    return r + offset


# four disjoint bars round an empty centre cell (1, 1): every row and
# every column of the 3 x 3 square lies in the projections of two bars,
# every bar meets both, and no cell is covered twice
PINWHEEL = [(0, 1, 0, 0), (2, 2, 0, 1), (1, 2, 2, 2), (0, 0, 1, 2)]


@pytest.mark.parametrize("scale, offset", [(1, 0), (3, 0), (2, 500)])
def test_keeps_a_group_whose_projections_reach_m_without_a_hot_cell(
        scale, offset):
    rect = _shift(PINWHEEL, scale, offset)
    cid = np.arange(4)
    keep = Q._large_groups_hot(rect, cid, np.array([4]), 2)
    assert keep.tolist() == [True]
    assert Q._sweep_text(rect, 2) == []


@pytest.mark.parametrize("scale, offset", [(1, 0), (3, 0), (2, 500)])
def test_keeps_a_group_with_one_hot_cell(scale, offset):
    # three coordinates meet in cell (4, 6) alone; four more rectangles,
    # three of them of those coordinates, lie apart from it
    rect = _shift([(2, 4, 6, 9), (4, 7, 3, 6), (0, 4, 6, 6),
                   (8, 9, 0, 1), (0, 1, 8, 9), (5, 9, 8, 9),
                   (0, 3, 0, 2)], scale, offset)
    cid = np.array([0, 1, 2, 0, 1, 2, 3])
    keep = Q._large_groups_hot(rect, cid, np.array([7]), 3)
    assert keep.tolist() == [True]
    lo = 4 * scale + offset
    hi = lo + scale - 1
    lo_y = 6 * scale + offset
    assert Q._sweep_text(rect, 3) == [(lo, hi, lo_y, lo_y + scale - 1)]


def test_rejects_groups_whose_hot_rows_and_columns_do_not_meet():
    # group 0: three coordinates share rows 0..2 and, apart, columns
    # 10..12, so each axis is hot, but no rectangle meets both hot sets
    # with two others; group 1 repeats the hot cell of the test above
    g0 = [(0, 2, 0, 0), (0, 2, 3, 3), (0, 2, 6, 6),
          (5, 5, 10, 12), (7, 7, 10, 12), (9, 9, 10, 12)]
    g1 = [(2, 4, 6, 9), (4, 7, 3, 6), (0, 4, 6, 6)]
    rect = np.asarray(g0 + g1, np.int64)
    cid = np.array([0, 1, 2, 0, 1, 2, 0, 1, 2])
    keep = Q._large_groups_hot(rect, cid, np.array([6, 3]), 3)
    assert keep.tolist() == [False, True]
    assert Q._sweep_text(rect[:6], 3) == []


# --------------------------------------------------------------------------
# (d) the batch paths, against looping query()
# --------------------------------------------------------------------------

def _index(kind, docs, tmp_path):
    builder = IndexBuilder(scheme=SCHEMES["multiset"](docs)).build(docs)
    if kind == "mutable":
        return builder
    frozen = builder.freeze()
    if kind == "live":
        save_index(frozen, tmp_path / "idx")
        return LiveIndex.open(tmp_path / "idx")
    return frozen


@pytest.mark.parametrize("kind", ["frozen", "mutable", "live"])
@pytest.mark.parametrize("plan", ["cpu", "device"])
def test_batch_query_equals_looping_query(plan, kind, tmp_path):
    docs, qs = _corpus(0)
    index = _index(kind, docs, tmp_path)
    frozen = IndexBuilder(scheme=SCHEMES["multiset"](docs)).build(
        docs).freeze()
    rect, cid, sizes = _large_groups(frozen, qs, 7)
    keep = Q._large_groups_hot(rect, cid, sizes, 7)
    assert keep.sum() > 0 and (~keep).sum() > 0
    reset_transfer_stats()
    opts = QueryOptions(plan=plan)
    got = (index.batch_query(qs, 0.8, options=opts) if kind == "live"
           else batch_query(index, qs, 0.8, options=opts))
    want = [query(frozen, q, 0.8) for q in qs]
    assert [[(a.text_id, a.blocks, a.ncoords) for a in r] for r in got] == \
        [[(a.text_id, a.blocks, a.ncoords) for a in r] for r in want]
    st = transfer_stats()
    if plan == "device":
        assert st["host_large_groups"] == len(sizes)
        assert st["host_large_rejected"] == (~keep).sum()


@pytest.mark.parametrize("plan", ["cpu", "device"])
def test_rows_reach_the_test_in_coordinate_and_start_order(plan,
                                                          monkeypatch):
    # the test sorts its rows only when they are not already ascending in
    # (coordinate, a) within each group; both plans hand them over so
    seen = []
    real = Q._large_groups_hot

    def spy(rect, cid, sizes, m):
        seen.append((np.asarray(rect), np.asarray(cid), np.asarray(sizes)))
        return real(rect, cid, sizes, m)

    monkeypatch.setattr(Q, "_large_groups_hot", spy)
    docs, qs = _corpus(1)
    frozen = IndexBuilder(scheme=SCHEMES["multiset"](docs)).build(
        docs).freeze()
    batch_query(frozen, qs, 0.8, options=QueryOptions(plan=plan))
    (rect, cid, sizes), = seen
    assert len(sizes) > 0
    grp = np.repeat(np.arange(len(sizes)), sizes)
    key = np.stack([grp, cid, rect[:, 0]])
    steps = np.diff(key, axis=1)
    ascending = (steps[0] > 0) | ((steps[0] == 0) & (
        (steps[1] > 0) | ((steps[1] == 0) & (steps[2] >= 0))))
    assert ascending.all()
