"""Fused probe arena: parity with the per-table probes on both re-keying
schemes, host-vs-resident probe equality, the grouped small-sweep
dispatcher, threaded-vs-serial sharded fan-out, and arena persistence."""

import json

import numpy as np
import pytest

from repro.core import (FrozenTable, IndexBuilder, MultisetScheme,
                        ProbeArena, SearchIndex, ShardedAlignmentIndex,
                        WeightedScheme, WeightFn, batch_query,
                        estimate_similarity, query)
from repro.core.frozen import KIND_EMPTY, MODE_COORD, MODE_PACKED
from repro.core.results import QueryOptions
from repro.core.query import _sweep_small_batch, _sweep_text

SCHEMES = {
    "multiset": lambda: MultisetScheme(seed=13, k=8),
    "mix": lambda: MultisetScheme(seed=13, k=8, family="mix"),
    "weighted": lambda: WeightedScheme(weight=WeightFn(tf="raw"), seed=21,
                                       k=8),
}


def _corpus(rng, n_docs=6, vocab=30, n=50):
    return [rng.integers(0, vocab, size=n).astype(np.int64)
            for _ in range(n_docs)]


def _queries(rng, docs, n=5):
    qs = [docs[i % len(docs)][5:30].copy() for i in range(n)]
    qs.append(rng.integers(1000, 1030, size=12).astype(np.int64))  # miss
    return qs


def _blocks(results):
    return [(a.text_id, a.blocks) for a in results]


def _frozen(kind, docs):
    return IndexBuilder(scheme=SCHEMES[kind]()).build(docs).freeze()


# --------------------------------------------------------------------------
# arena layout + probe parity with the per-table path
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(SCHEMES))
def test_arena_mode_selection_and_layout(kind):
    rng = np.random.default_rng(0)
    frozen = _frozen(kind, _corpus(rng))
    arena = frozen.arena()
    # 61/64-bit multiset hashes overflow (coord << 56); ICWS pair keys with
    # a small vocabulary pack
    assert arena.mode == (MODE_PACKED if kind == "weighted" else MODE_COORD)
    assert arena.keys.dtype == np.uint64
    assert len(arena.keys) == sum(len(t) for t in frozen.tables)
    assert arena.offsets[0] == 0
    assert arena.offsets[-1] == len(arena.windows)
    assert len(arena.windows) == sum(len(t.windows) for t in frozen.tables)
    if arena.mode == MODE_PACKED:
        assert np.all(arena.keys[:-1] < arena.keys[1:])   # globally sorted
        assert len(arena.coords) == 0
    else:
        assert np.all(arena.keys[:-1] <= arena.keys[1:])
        tie = arena.keys[:-1] == arena.keys[1:]
        assert np.all(arena.coords[:-1][tie] < arena.coords[1:][tie])


@pytest.mark.parametrize("kind", list(SCHEMES))
@pytest.mark.parametrize("backend", ["numpy", "device"])
def test_arena_probe_matches_per_table_probe(kind, backend):
    rng = np.random.default_rng(1)
    docs = _corpus(rng)
    frozen = _frozen(kind, docs)
    arena = frozen.arena()
    k = arena.k
    sketches = frozen.scheme.sketch_batch(_queries(rng, docs))
    pkeys, coords, valid = arena.encode_batch(sketches)
    if backend == "device":          # the device plan's resident arena
        from repro.core.device_plan import resident_probe
        starts, ends = resident_probe(frozen, pkeys, coords, valid)
    else:
        starts, ends = arena.probe(pkeys, coords, valid)
    for b, sk in enumerate(sketches):
        for i in range(k):
            table = frozen.tables[i]
            ts, te = table.probe(table.encode([sk[i]]))
            rows_table = table.windows[ts[0]:te[0]]
            p = b * k + i
            rows_arena = arena.windows[starts[p]:ends[p]]
            assert np.array_equal(np.asarray(rows_arena),
                                  np.asarray(rows_table)), (b, i)


def test_arena_coord_mode_on_packable_keys_agrees():
    """Force the coord layout onto pair tables (packable) — both schemes
    must resolve every probe to the same posting rows."""
    rng = np.random.default_rng(2)
    docs = _corpus(rng)
    frozen = _frozen("weighted", docs)
    packed = ProbeArena.from_tables(frozen.tables, mode=MODE_PACKED)
    coord = ProbeArena.from_tables(frozen.tables, mode=MODE_COORD)
    sketches = frozen.scheme.sketch_batch(_queries(rng, docs))
    for arena in (packed, coord):
        pk, co, va = arena.encode_batch(sketches)
        s, e = arena.probe(pk, co, va)
        arena_rows = [np.asarray(arena.windows[s[p]:e[p]])
                      for p in range(len(pk))]
        if arena is packed:
            want = arena_rows
        else:
            assert all(np.array_equal(a, b)
                       for a, b in zip(arena_rows, want))


def test_arena_unpackable_probe_keys_miss():
    rng = np.random.default_rng(3)
    frozen = _frozen("weighted", _corpus(rng))
    arena = frozen.arena()
    k = arena.k
    # out-of-range tokens / k_int spans cannot equal any stored key
    bad = [[(1 << 40, 0)] * k, [(-5, 0)] * k, [(3, 1 << 40)] * k,
           [(3, -(1 << 40))] * k]
    pk, co, valid = arena.encode_batch(bad)
    assert not valid.any()
    s, e = arena.probe(pk, co, valid)
    assert not (e > s).any()


def test_arena_with_empty_tables():
    t_real = FrozenTable.from_dict({7: [(0, 0, 1, 0, 1)],
                                    9: [(1, 2, 3, 2, 3)]})
    t_empty = FrozenTable.from_dict({})
    assert t_empty.kind == KIND_EMPTY
    arena = ProbeArena.from_tables([t_real, t_empty])
    assert len(arena.keys) == 2
    pk, co, valid = arena.encode_batch([[7, 7], [9, 9], [8, 8]])
    # probes against the empty coordinate are invalid, hence misses
    assert list(valid) == [True, False, True, False, True, False]
    s, e = arena.probe(pk, co, valid)
    assert list((e - s)) == [1, 0, 1, 0, 0, 0]


def test_arena_probe_is_one_searchsorted(monkeypatch):
    rng = np.random.default_rng(4)
    docs = _corpus(rng)
    frozen = _frozen("multiset", docs)
    arena = frozen.arena()
    assert arena.max_run == 1     # independent hash functions rarely collide
    sketches = frozen.scheme.sketch_batch(_queries(rng, docs))
    pk, co, va = arena.encode_batch(sketches)
    calls = []
    real = np.searchsorted
    monkeypatch.setattr(np, "searchsorted",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    arena.probe(pk, co, va)
    assert len(calls) == 1


# --------------------------------------------------------------------------
# batched query engine over the arena
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(SCHEMES))
@pytest.mark.parametrize("theta", [0.3, 0.6, 1.0])
def test_batch_query_backends_equal_looped_query(kind, theta):
    rng = np.random.default_rng(5)
    docs = _corpus(rng)
    qs = _queries(rng, docs)
    builder = IndexBuilder(scheme=SCHEMES[kind]()).build(docs)
    frozen = builder.freeze()
    looped = [_blocks(query(builder, q, theta)) for q in qs]
    for plan in ("cpu", "device"):
        for index in (frozen, builder):
            got = [_blocks(r) for r in batch_query(
                index, qs, theta, options=QueryOptions(plan=plan))]
            assert got == looped, (plan, index.is_frozen)


def test_batch_query_empty_batch_and_all_miss():
    rng = np.random.default_rng(6)
    frozen = _frozen("multiset", _corpus(rng, n_docs=2))
    assert batch_query(frozen, [], 0.5) == []
    miss = [rng.integers(500, 520, 10).astype(np.int64)]
    assert batch_query(frozen, miss, 0.5) == [[]]


def test_sweep_small_batch_matches_sweep_text_randomized():
    rng = np.random.default_rng(7)
    for _ in range(40):
        m = int(rng.integers(1, 5))
        groups = []
        for _g in range(int(rng.integers(1, 10))):
            s = int(rng.integers(max(1, m), 17))
            lim = int(rng.integers(2, 10))    # tiny space -> duplicate and
            a = rng.integers(0, lim, s)       # zero-width boundaries
            b = a + rng.integers(0, lim, s)
            c = rng.integers(0, lim, s)
            d = c + rng.integers(0, lim, s)
            groups.append(np.stack([a, b, c, d], 1).astype(np.int64))
        sizes = np.array([len(g) for g in groups])
        arr = np.zeros((len(groups), int(sizes.max()), 4), np.int64)
        for g, wins in enumerate(groups):
            arr[g, :len(wins)] = wins
        assert _sweep_small_batch(arr, sizes, m) == \
            [_sweep_text(g, m) for g in groups]


# --------------------------------------------------------------------------
# sharded fan-out
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["multiset", "weighted"])
def test_sharded_threaded_equals_serial(kind):
    rng = np.random.default_rng(8)
    docs = _corpus(rng, n_docs=9)
    qs = _queries(rng, docs, n=4)
    sharded = ShardedAlignmentIndex(scheme=SCHEMES[kind](),
                                    n_shards=3).build(docs)
    looped = [_blocks(sharded.query(q, 0.5)) for q in qs]
    sharded.freeze()
    serial = [_blocks(r) for r in sharded.batch_query(
        qs, 0.5, options=QueryOptions(fanout="serial"))]
    threaded = [_blocks(r) for r in sharded.batch_query(
        qs, 0.5, options=QueryOptions(fanout="threaded"))]
    assert serial == threaded == looped


# --------------------------------------------------------------------------
# persistence
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["multiset", "weighted"])
def test_store_roundtrip_persists_mmap_arena(tmp_path, kind):
    rng = np.random.default_rng(9)
    docs = _corpus(rng)
    qs = _queries(rng, docs, n=3)
    frozen = _frozen(kind, docs)
    want = [_blocks(r) for r in batch_query(frozen, qs, 0.5)]
    frozen.save(tmp_path)
    assert (tmp_path / "arena.keys.npy").exists()
    loaded = SearchIndex.load(tmp_path, mmap=True)
    assert loaded._arena is not None          # restored, not rebuilt
    assert isinstance(loaded._arena.keys, np.memmap)
    assert isinstance(loaded._arena.windows, np.memmap)
    assert loaded._arena.mode == frozen.arena().mode
    assert [_blocks(r) for r in batch_query(loaded, qs, 0.5)] == want


def test_pre_arena_store_rebuilds_lazily(tmp_path):
    rng = np.random.default_rng(10)
    docs = _corpus(rng)
    qs = _queries(rng, docs, n=3)
    frozen = _frozen("multiset", docs)
    want = [_blocks(r) for r in batch_query(frozen, qs, 0.5)]
    frozen.save(tmp_path)
    # simulate a pre-arena store: no arena files, and a manifest that
    # never knew about them (no arena entry, no arena checksums)
    for p in tmp_path.glob("arena.*.npy"):
        p.unlink()  # repro: allow[RPR203] (pre-arena fixture)
    manifest = json.loads((tmp_path / "manifest.json").read_text())
    manifest.pop("arena", None)
    manifest["checksums"] = {f: rec for f, rec in
                             manifest.get("checksums", {}).items()
                             if not f.startswith("arena.")}
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))  # repro: allow[RPR202,RPR203]
    loaded = SearchIndex.load(tmp_path, mmap=True)
    assert loaded._arena is None
    assert [_blocks(r) for r in batch_query(loaded, qs, 0.5)] == want
    assert loaded._arena is not None          # built on first batch


def test_sharded_restore_keeps_per_shard_arenas(tmp_path):
    rng = np.random.default_rng(11)
    docs = _corpus(rng, n_docs=9)
    qs = _queries(rng, docs, n=3)
    sharded = ShardedAlignmentIndex(scheme=SCHEMES["multiset"](),
                                    n_shards=3).build(docs).freeze()
    want = [_blocks(r) for r in sharded.batch_query(qs, 0.5)]
    sharded.save(tmp_path)
    restored = ShardedAlignmentIndex(scheme=SCHEMES["multiset"](),
                                     n_shards=3)
    assert restored.restore(tmp_path, mmap=True) == []
    assert all(s._arena is not None for s in restored.shards)
    assert [_blocks(r) for r in restored.batch_query(qs, 0.5)] == want


# --------------------------------------------------------------------------
# estimate_similarity vectorization
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(SCHEMES))
def test_estimate_similarity_matches_scalar_reference(kind):
    rng = np.random.default_rng(12)
    docs = _corpus(rng, n_docs=2, n=40)
    idx = IndexBuilder(scheme=SCHEMES[kind]()).build(docs)
    for other in (docs[1], docs[0][5:30], docs[0]):
        got = estimate_similarity(idx, docs[0], other)
        sq = idx.scheme.sketch(docs[0])
        sd = idx.scheme.sketch(other)
        want = float(np.mean([1.0 if x == y else 0.0
                              for x, y in zip(sq, sd)]))
        assert got == want
    assert estimate_similarity(idx, docs[0], docs[0]) == 1.0
