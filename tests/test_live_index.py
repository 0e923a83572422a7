"""PR 5 live incremental serve: `LiveIndex` (frozen mmap store + mutable
delta), columnar merge-compaction into promoted store generations, the
sharded per-shard deltas with process fan-out, and crash-safety of
promotion (an interrupted compaction must never corrupt serving)."""

import shutil
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro import fault
from repro.api import Aligner
from repro.core import (IndexBuilder, QueryOptions, ShardedAlignmentIndex,
                        batch_query, make_scheme, query, save_index)
from repro.core import store as index_store
from repro.core.live import LiveIndex
from repro.core.store import (CURRENT_POINTER, current_generation,
                              promote_generation, resolve_store)

SIMS = ["multiset", "tfidf"]


def _corpus(rng, n_docs=8, vocab=40, n=60):
    docs = [rng.integers(0, vocab, size=n).astype(np.int64)
            for _ in range(n_docs)]
    if n_docs > 5:
        docs[5] = docs[2].copy()                  # planted duplicate
    return docs


def _scheme(similarity, docs):
    kw = {"corpus": docs} if similarity == "tfidf" else {}
    return make_scheme(similarity, seed=5, k=8, **kw)


def _blocks(results):
    return [(a.text_id, a.blocks) for a in results]


def _batch_blocks(res):
    return [_blocks(r) for r in res]


def _save_flat(scheme, docs, path):
    save_index(IndexBuilder(scheme=scheme).build(docs).freeze(), path)


def _delta_docs(rng, base, n=3):
    docs = [rng.integers(0, 40, size=60).astype(np.int64) for _ in range(n)]
    docs[-1] = base[2].copy()                     # near-dup into the delta
    return docs


def _queries(rng, base, delta):
    return [base[2][5:50], delta[-1][:30],
            rng.integers(1000, 1040, 20).astype(np.int64)]     # + a miss


# --------------------------------------------------------------------------
# LiveIndex == from-scratch build of the union corpus (the core contract)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("similarity", SIMS)
@pytest.mark.parametrize("mmap", [True, False])
def test_live_matches_scratch_build(tmp_path, similarity, mmap):
    rng = np.random.default_rng(0)
    base = _corpus(rng)
    scheme = _scheme(similarity, base)
    _save_flat(scheme, base, tmp_path / "idx")

    live = LiveIndex.open(tmp_path / "idx", mmap=mmap)
    delta = _delta_docs(rng, base)
    for t in delta:
        live.add_text(t)
    assert live.num_texts == len(base) + len(delta)
    assert live.delta_fraction == pytest.approx(3 / 11)

    oracle = IndexBuilder(scheme=scheme).build(base + delta)
    qs = _queries(rng, base, delta)
    expected = _batch_blocks(batch_query(oracle, qs, 0.5))
    # frozen + delta merge, before compaction
    assert _batch_blocks(live.batch_query(qs, 0.5)) == expected
    # the single-query path agrees too
    assert _blocks(live.query(qs[0], 0.5)) == \
        _blocks(query(oracle, qs[0], 0.5))

    gen = live.compact()
    assert gen == 1 and live.generation == 1
    assert live.delta.num_texts == 0 and live.frozen.num_texts == 11
    assert _batch_blocks(live.batch_query(qs, 0.5)) == expected

    # a fresh reader resolves the promoted generation
    again = LiveIndex.open(tmp_path / "idx", mmap=mmap)
    assert again.generation == 1
    assert again.frozen.is_mmap() == mmap
    assert _batch_blocks(again.batch_query(qs, 0.5)) == expected

    # second round over the compacted base: add more, still block-identical
    more = _delta_docs(rng, base, n=2)
    for t in more:
        again.add_text(t)
    oracle2 = IndexBuilder(scheme=scheme).build(base + delta + more)
    expected2 = _batch_blocks(batch_query(oracle2, qs, 0.5))
    assert _batch_blocks(again.batch_query(qs, 0.5)) == expected2
    again.compact()
    assert again.generation == 2
    assert _batch_blocks(again.batch_query(qs, 0.5)) == expected2


@pytest.mark.parametrize("plan", ["cpu", "device"])
def test_live_probe_backends_agree(tmp_path, plan):
    rng = np.random.default_rng(2)
    base = _corpus(rng)
    scheme = _scheme("multiset", base)
    _save_flat(scheme, base, tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    delta = _delta_docs(rng, base)
    for t in delta:
        live.add_text(t)
    qs = _queries(rng, base, delta)
    oracle = IndexBuilder(scheme=scheme).build(base + delta)
    assert _batch_blocks(
        live.batch_query(qs, 0.5,
                         options=QueryOptions(plan=plan))) == \
        _batch_blocks(batch_query(oracle, qs, 0.5))


def test_live_compacted_store_identical_to_scratch_store(tmp_path):
    """The compacted generation's arrays are bit-identical to freezing a
    from-scratch build of the union corpus (not just result-identical)."""
    rng = np.random.default_rng(3)
    base = _corpus(rng)
    scheme = _scheme("multiset", base)
    _save_flat(scheme, base, tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    delta = _delta_docs(rng, base)
    for t in delta:
        live.add_text(t)
    live.compact()

    scratch = IndexBuilder(scheme=scheme).build(base + delta).freeze()
    for ta, tb in zip(live.frozen.tables, scratch.tables):
        assert ta.kind == tb.kind and ta.kint_min == tb.kint_min
        assert np.array_equal(ta.keys, tb.keys)
        assert np.array_equal(ta.offsets, tb.offsets)
        assert np.array_equal(ta.windows, tb.windows)
    aa, ab = live.frozen.arena(), scratch.arena()
    assert aa.mode == ab.mode
    assert np.array_equal(aa.keys, ab.keys)
    assert np.array_equal(aa.offsets, ab.offsets)
    assert np.array_equal(aa.windows, ab.windows)


def test_live_freeze_merges_in_memory(tmp_path):
    rng = np.random.default_rng(4)
    base = _corpus(rng)
    scheme = _scheme("multiset", base)
    _save_flat(scheme, base, tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    delta = _delta_docs(rng, base)
    for t in delta:
        live.add_text(t)
    merged = live.freeze()
    assert merged.is_frozen and merged.num_texts == 11
    # the on-disk store is untouched (no generation written)
    assert current_generation(tmp_path / "idx") == 0
    qs = _queries(rng, base, delta)
    assert _batch_blocks(batch_query(merged, qs, 0.5)) == \
        _batch_blocks(live.batch_query(qs, 0.5))


# --------------------------------------------------------------------------
# sharded live serving (per-shard deltas, process-pool compaction)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("similarity", SIMS)
@pytest.mark.parametrize("fanout,mmap", [("process", True),
                                         ("serial", False)])
def test_sharded_live_matches_scratch(tmp_path, similarity, fanout, mmap):
    rng = np.random.default_rng(5)
    base = _corpus(rng, n_docs=9)
    a = Aligner.build(base, similarity=similarity, k=8, seed=5, shards=3)
    a.save(tmp_path / "sh")

    live = Aligner.load(tmp_path / "sh", live=True, mmap=mmap)
    delta = _delta_docs(rng, base, n=4)
    assert [live.add(t) for t in delta] == [9, 10, 11, 12]

    oracle = ShardedAlignmentIndex(scheme=live.scheme, n_shards=3)
    for t in base + delta:
        oracle.add_text(t)
    qs = _queries(rng, base, delta)
    expected = _batch_blocks(oracle.batch_query(qs, 0.5))
    assert _batch_blocks(live.find_batch(qs, 0.5)) == expected

    live.compact(fanout=fanout)
    assert all(s.generation == 1 and s.delta.num_texts == 0
               for s in live._index.shards)
    assert _batch_blocks(live.find_batch(qs, 0.5)) == expected

    # both reader modes see the promoted generations
    for live_reload in (True, False):
        again = Aligner.load(tmp_path / "sh", live=live_reload, mmap=mmap)
        assert again.num_docs == 13
        assert _batch_blocks(again.find_batch(qs, 0.5)) == expected


def test_sharded_restore_remaps_doc_ids_via_store_manifests(tmp_path):
    """The per-shard store manifests are authoritative for global doc ids:
    a shard compacted (with new docs) after meta.json was written still
    restores correctly — no contiguity assumption on shard-local ids."""
    rng = np.random.default_rng(6)
    base = _corpus(rng, n_docs=9)
    a = Aligner.build(base, similarity="multiset", k=8, seed=6, shards=3)
    a.save(tmp_path / "sh")
    stale_meta = (tmp_path / "sh" / "meta.json").read_bytes()

    live = Aligner.load(tmp_path / "sh", live=True)
    delta = _delta_docs(rng, base, n=4)
    for t in delta:
        live.add(t)
    live.compact()
    qs = _queries(rng, base, delta)
    expected = _batch_blocks(live.find_batch(qs, 0.5))

    # simulate the crash window between shard promotion and the root
    # meta.json rewrite: the stale meta knows nothing of the delta docs
    (tmp_path / "sh" / "meta.json").write_bytes(stale_meta)  # repro: allow[RPR203]
    again = Aligner.load(tmp_path / "sh", live=True)
    assert again.num_docs == 13          # rebuilt from the shard manifests
    assert _batch_blocks(again.find_batch(qs, 0.5)) == expected


def test_sharded_live_save_snapshots_merged_store(tmp_path):
    rng = np.random.default_rng(7)
    base = _corpus(rng, n_docs=9)
    a = Aligner.build(base, similarity="multiset", k=8, seed=7, shards=3)
    a.save(tmp_path / "sh")
    live = Aligner.load(tmp_path / "sh", live=True)
    delta = _delta_docs(rng, base, n=4)
    for t in delta:
        live.add(t)
    qs = _queries(rng, base, delta)
    expected = _batch_blocks(live.find_batch(qs, 0.5))
    live.save(tmp_path / "snap")                  # frozen+delta, one pass
    served = Aligner.load(tmp_path / "snap")
    assert served.num_docs == 13
    assert _batch_blocks(served.find_batch(qs, 0.5)) == expected
    # the snapshot did not disturb the serving aligner: still live, delta
    # intact, still taking writes
    assert all(getattr(s, "is_live", False) for s in live._index.shards)
    assert live.add(_delta_docs(rng, base, n=1)[0]) == 13
    assert _batch_blocks(live.find_batch(qs, 0.5)) != []


# --------------------------------------------------------------------------
# promotion crash-safety & rollback
# --------------------------------------------------------------------------

def _live_with_delta(tmp_path, rng):
    base = _corpus(rng)
    scheme = _scheme("multiset", base)
    _save_flat(scheme, base, tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    delta = _delta_docs(rng, base)
    for t in delta:
        live.add_text(t)
    return base, delta, live


def _compaction_site_schedule():
    """Enumerate every fsio fault checkpoint one compaction of the
    reference corpus hits, as ``(site, occurrence)`` pairs — recorded
    once at collection time so the sweep below parametrizes over ALL of
    them (new fsio call sites in the compaction path are swept
    automatically; hand-picked kill sites can't rot)."""
    tmp = Path(tempfile.mkdtemp())
    try:
        rng = np.random.default_rng(8)
        _base, _delta, live = _live_with_delta(tmp, rng)
        with fault.record_sites() as sites:
            assert live.compact() == 1
        return sorted(set(sites))
    finally:
        shutil.rmtree(tmp)


_COMPACTION_SITES = _compaction_site_schedule()


@pytest.mark.parametrize(
    "site,hit", _COMPACTION_SITES,
    ids=[f"{s}@{h}" for s, h in _COMPACTION_SITES])
def test_interrupted_compaction_preserves_serving(tmp_path, site, hit):
    """Fail compaction at EVERY fsio checkpoint it crosses — array
    writes, manifest tmp/rename, pointer tmp/rename: the serving
    generation must be untouched, a fresh reader must load it
    identically, and retrying the compaction must succeed."""
    rng = np.random.default_rng(8)
    base, delta, live = _live_with_delta(tmp_path, rng)
    qs = _queries(rng, base, delta)
    expected_live = _batch_blocks(live.batch_query(qs, 0.5))
    frozen_before = _batch_blocks(
        batch_query(live.frozen, qs, 0.5))

    plan = fault.FaultPlan(triggers=[fault.Trigger(site=site, hit=hit)])
    with fault.armed(plan):
        with pytest.raises(fault.FaultInjected):
            live.compact()

    # the pointer never flipped; pre-promote failures leave no manifest
    root = tmp_path / "idx"
    assert current_generation(root) == 0
    assert resolve_store(root) == root
    if not site.startswith("store.promote"):
        assert not (root / "v000001" / "manifest.json").exists()
    # the live index kept the docs (delta restored, or still sealed when
    # the failure hit after the merge) and still serves the union
    if site.startswith("store.promote"):
        assert live.sealed is not None
        assert live.sealed.num_texts == len(delta)
    else:
        assert live.sealed is None
        assert live.delta.num_texts == len(delta)
    assert _batch_blocks(live.batch_query(qs, 0.5)) == expected_live
    # a fresh (non-live) reader serves the old generation, bit-for-bit
    reader = Aligner.load(root)
    assert _batch_blocks(reader.find_batch(qs, 0.5)) == frozen_before

    # retry converges: a clean commit over (or past) the aborted dir
    gen = live.compact()
    assert gen >= 1
    assert current_generation(root) == gen
    assert _batch_blocks(live.batch_query(qs, 0.5)) == expected_live


def test_promote_refuses_manifestless_generation(tmp_path):
    rng = np.random.default_rng(9)
    _base, _delta, live = _live_with_delta(tmp_path, rng)
    root = tmp_path / "idx"
    (root / "v000001").mkdir()                 # aborted write: arrays only
    with pytest.raises(ValueError, match="no manifest"):
        promote_generation(root, 1)
    with pytest.raises(ValueError, match="generation 0"):
        promote_generation(root, 0)
    # a hand-corrupted pointer is rejected loudly, not served stale
    (root / CURRENT_POINTER).write_text("v000042")  # repro: allow[RPR202,RPR203]
    with pytest.raises(ValueError, match="v000042"):
        resolve_store(root)
    (root / CURRENT_POINTER).unlink()  # repro: allow[RPR203] (fixture reset)
    assert live.compact() == 1                 # still compacts cleanly


def test_rollback_to_retained_generation(tmp_path):
    rng = np.random.default_rng(10)
    base, delta, live = _live_with_delta(tmp_path, rng)
    root = tmp_path / "idx"
    qs = _queries(rng, base, delta)
    live.compact()                             # gen 1 = base + delta
    gen1 = _batch_blocks(Aligner.load(root).find_batch(qs, 0.5))
    for t in _delta_docs(rng, base, n=2):
        live.add_text(t)
    live.compact()                             # gen 2 = gen1 + 2 docs
    assert current_generation(root) == 2

    promote_generation(root, 1)                # operator rollback
    assert current_generation(root) == 1
    assert _batch_blocks(Aligner.load(root).find_batch(qs, 0.5)) == gen1
    rolled = LiveIndex.open(root)
    assert rolled.frozen.num_texts == len(base) + len(delta)


def test_compact_with_empty_delta_is_noop(tmp_path):
    """Nothing to fold in -> no new generation (a timer-driven compactor
    must not duplicate the whole corpus on every tick)."""
    rng = np.random.default_rng(16)
    base = _corpus(rng)
    scheme = _scheme("multiset", base)
    _save_flat(scheme, base, tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    assert live.compact() == 0
    assert not (tmp_path / "idx" / "v000001").exists()
    live.add_text(base[2].copy())
    assert live.compact() == 1
    assert live.compact() == 1                 # empty again: still a no-op
    assert not (tmp_path / "idx" / "v000002").exists()

    # sharded: only the shard that actually took a write is compacted
    a = Aligner.build(base, similarity="multiset", k=8, seed=16, shards=3)
    a.save(tmp_path / "sh")
    sh = Aligner.load(tmp_path / "sh", live=True)
    sh.compact()                               # all deltas empty: no-op
    assert all(s.generation == 0 for s in sh._index.shards)
    gid = sh.add(base[2].copy())               # lands in shard gid % 3
    sh.compact()
    gens = [s.generation for s in sh._index.shards]
    assert gens[gid % 3] == 1
    assert sum(gens) == 1                      # untouched shards stayed put
    hits = sh.find(base[2][5:50], 0.5)
    assert {h.text_id for h in hits} >= {2, 5, gid}


def test_compact_after_rollback_never_renumbers_retained_gen(tmp_path):
    """A promoted generation is immutable: after a rollback, the next
    compaction takes a FRESH number instead of rewriting the rolled-off
    version (whose arrays may still be mmap'd by running readers)."""
    rng = np.random.default_rng(12)
    base, _delta, live = _live_with_delta(tmp_path, rng)
    root = tmp_path / "idx"
    live.compact()                             # v000001
    for t in _delta_docs(rng, base, n=2):
        live.add_text(t)
    live.compact()                             # v000002
    v2_manifest = (root / "v000002" / "manifest.json").read_bytes()

    promote_generation(root, 1)                # rollback
    rolled = LiveIndex.open(root)
    for t in _delta_docs(rng, base, n=1):
        rolled.add_text(t)
    assert rolled.compact() == 3               # not 2!
    assert (root / "v000002" / "manifest.json").read_bytes() == v2_manifest
    assert current_generation(root) == 3


def test_live_save_refuses_overwriting_served_store(tmp_path):
    rng = np.random.default_rng(13)
    base, _delta, live_idx = _live_with_delta(tmp_path, rng)
    root = tmp_path / "idx"
    live = Aligner.load(root, live=True)
    live.add(base[2].copy())
    with pytest.raises(RuntimeError, match="serving from"):
        live.save(root)
    # sharded: same refusal on the shared store root
    a = Aligner.build(base, similarity="multiset", k=8, seed=13, shards=2)
    a.save(tmp_path / "sh")
    sh = Aligner.load(tmp_path / "sh", live=True)
    sh.add(base[2].copy())
    with pytest.raises(RuntimeError, match="serving from"):
        sh.save(tmp_path / "sh")
    del live_idx


def test_live_save_retires_stale_pointer_at_target(tmp_path):
    """Snapshotting onto a directory that used to be a versioned live
    store must retire its CURRENT pointer — otherwise the old generation
    silently shadows the fresh flat snapshot on reload."""
    rng = np.random.default_rng(14)
    base, _delta, old_live = _live_with_delta(tmp_path, rng)
    target = tmp_path / "idx"
    old_live.compact()                         # target now has CURRENT
    assert current_generation(target) == 1

    fresh_docs = _corpus(np.random.default_rng(15), n_docs=6)
    b = Aligner.build(fresh_docs, similarity="multiset", k=8, seed=14)
    b.save(tmp_path / "b")
    live_b = Aligner.load(tmp_path / "b", live=True)
    live_b.add(fresh_docs[1].copy())
    qs = [fresh_docs[1][:40]]
    expected = _batch_blocks(live_b.find_batch(qs, 0.5))
    live_b.save(target)                        # different store: allowed
    assert not (target / CURRENT_POINTER).exists()
    served = Aligner.load(target)
    assert served.num_docs == 7
    assert _batch_blocks(served.find_batch(qs, 0.5)) == expected


def test_store_helpers_and_is_index_store(tmp_path):
    rng = np.random.default_rng(11)
    _base, _delta, live = _live_with_delta(tmp_path, rng)
    root = tmp_path / "idx"
    assert index_store.is_index_store(root)
    live.compact()
    assert index_store.is_index_store(root)
    assert resolve_store(root) == root / "v000001"
    # read_manifest follows the pointer: the serving manifest has 11 texts
    assert index_store.read_manifest(root)["num_texts"] == 11
    assert not index_store.is_index_store(tmp_path / "nowhere")
