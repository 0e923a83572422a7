"""Execution plans + the device-resident query pipeline (PR 10).

The contract under test: ``plan="device"`` is *block-for-block identical*
to ``plan="cpu"`` on every scheme (the kernels run in interpret mode on
CPU CI), the ProbeArena goes device-resident at most once per store
generation (and re-uploads exactly once when compaction/promotion swaps
the generation), the mutable live delta level transparently keeps the
host probe, every store kind runs the one batch pipeline (the frozen base
of a live store and every shard of a sharded store take the run path),
``plan="auto"`` downgrades silently when no accelerator backs jax, and
the legacy kwargs still work one release behind a ``DeprecationWarning``
that names the removal release.
"""

import importlib
import warnings

import numpy as np
import pytest

from repro.api import Aligner
from repro.core import (IndexBuilder, LiveIndex, MultisetScheme,
                        QueryOptions, ShardedAlignmentIndex, WeightedScheme,
                        WeightFn, batch_query, make_scheme, resolve_plan,
                        save_index)
from repro.core import device_plan as dp
from repro.core.device_plan import (device_arena, reset_transfer_stats,
                                    resident_probe, transfer_stats)

Q = importlib.import_module("repro.core.query")

SCHEMES = {
    "multiset": lambda docs: MultisetScheme(seed=13, k=8),
    "weighted": lambda docs: WeightedScheme(weight=WeightFn(tf="raw"),
                                            seed=21, k=8),
    "tfidf": lambda docs: make_scheme("tfidf", seed=5, k=8, corpus=docs),
}


def _corpus(rng, n_docs=6, vocab=30, n=50):
    docs = [rng.integers(0, vocab, size=n).astype(np.int64)
            for _ in range(n_docs)]
    docs[-1] = docs[1].copy()                     # planted duplicate
    return docs


def _queries(rng, docs, n=5):
    qs = [docs[i % len(docs)][5:30].copy() for i in range(n)]
    qs.append(rng.integers(1000, 1030, size=12).astype(np.int64))  # miss
    return qs


def _blocks(results):
    return [(a.text_id, a.blocks) for a in results]


def _batch_blocks(res):
    return [_blocks(r) for r in res]


def _frozen(kind, docs):
    return IndexBuilder(scheme=SCHEMES[kind](docs)).build(docs).freeze()


# --------------------------------------------------------------------------
# bit parity: plan="device" == plan="cpu", block for block
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(SCHEMES))
@pytest.mark.parametrize("theta", [0.3, 0.6, 1.0])
def test_device_plan_matches_cpu_plan(kind, theta):
    rng = np.random.default_rng(0)
    docs = _corpus(rng)
    frozen = _frozen(kind, docs)
    qs = _queries(rng, docs)
    cpu = batch_query(frozen, qs, theta, options=QueryOptions(plan="cpu"))
    dev = batch_query(frozen, qs, theta, options=QueryOptions(plan="device"))
    assert _batch_blocks(dev) == _batch_blocks(cpu)
    # ncoords (the similarity numerator) survives the fused path too
    assert [[a.ncoords for a in r] for r in dev] == \
        [[a.ncoords for a in r] for r in cpu]


@pytest.mark.parametrize("kind", ["multiset", "weighted"])
def test_resident_probe_matches_host_probe(kind):
    # both arena key layouts: weighted packs (coord << 56) | key, multiset's
    # wide hashes carry the coordinate as a separate tag word
    rng = np.random.default_rng(1)
    docs = _corpus(rng)
    frozen = _frozen(kind, docs)
    arena = frozen.arena()
    sketches = frozen.scheme.sketch_batch(_queries(rng, docs))
    pk, co, va = arena.encode_batch(sketches)
    host_s, host_e = arena.probe(pk, co, va)
    dev_s, dev_e = resident_probe(frozen, pk, co, va)
    assert np.array_equal(dev_s, host_s)
    assert np.array_equal(dev_e, host_e)


def test_device_plan_on_mutable_builder_falls_back_to_host_probe():
    # the resident probe needs a frozen index; a dict-table builder under
    # plan="device" still answers (host per-coordinate probe, device sweep)
    rng = np.random.default_rng(2)
    docs = _corpus(rng)
    builder = IndexBuilder(scheme=MultisetScheme(seed=13, k=8)).build(docs)
    qs = _queries(rng, docs)
    cpu = batch_query(builder, qs, 0.5, options=QueryOptions(plan="cpu"))
    dev = batch_query(builder, qs, 0.5, options=QueryOptions(plan="device"))
    assert _batch_blocks(dev) == _batch_blocks(cpu)


# --------------------------------------------------------------------------
# residency: one upload per store generation
# --------------------------------------------------------------------------

def test_arena_uploads_once_across_batches():
    rng = np.random.default_rng(3)
    docs = _corpus(rng)
    frozen = _frozen("multiset", docs)
    qs = _queries(rng, docs)
    opts = QueryOptions(plan="device")
    reset_transfer_stats()
    for _ in range(3):
        batch_query(frozen, qs, 0.5, options=opts)
    st = transfer_stats()
    assert st["batches"] == 3
    assert st["arena_uploads"] == 1               # resident, not re-sent
    assert st["arena_bytes"] > 0
    # steady-state per-batch traffic excludes the arena: strictly smaller
    # than re-uploading it every batch would be
    assert st["h2d_bytes"] < 3 * st["arena_bytes"] + st["arena_bytes"]
    # the cache is keyed by arena identity on the index instance
    assert frozen._device_arena[0] is frozen.arena()
    assert device_arena(frozen) is frozen._device_arena[1]


def test_residency_invalidated_by_compaction(tmp_path):
    rng = np.random.default_rng(4)
    base = _corpus(rng, n_docs=8)
    scheme = MultisetScheme(seed=13, k=8)
    save_index(IndexBuilder(scheme=scheme).build(base).freeze(),
               tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    qs = _queries(rng, base)
    opts = QueryOptions(plan="device")

    reset_transfer_stats()
    first = live.batch_query(qs, 0.5, options=opts)
    live.batch_query(qs, 0.5, options=opts)
    assert transfer_stats()["arena_uploads"] == 1

    # promotion swaps in a new SearchIndex generation: exactly one more
    # upload, and the old residency can never serve the new generation
    extra = [base[2].copy(), rng.integers(0, 30, 50).astype(np.int64)]
    for t in extra:
        live.add_text(t)
    live.compact()
    assert live.generation == 1
    live.batch_query(qs, 0.5, options=opts)
    live.batch_query(qs, 0.5, options=opts)
    assert transfer_stats()["arena_uploads"] == 2

    oracle = IndexBuilder(scheme=scheme).build(base + extra)
    assert _batch_blocks(live.batch_query(qs, 0.5, options=opts)) == \
        _batch_blocks(batch_query(oracle, qs, 0.5))
    assert _batch_blocks(first) == \
        _batch_blocks(batch_query(IndexBuilder(scheme=scheme).build(base),
                                  qs, 0.5))


def test_oversized_arena_caches_host_fallback(monkeypatch):
    # an arena the device probe cannot address is an error under the
    # device plan, never a batch quietly served on the host; the cpu plan
    # still serves the same store
    rng = np.random.default_rng(5)
    docs = _corpus(rng)
    frozen = _frozen("multiset", docs)
    qs = _queries(rng, docs)
    cpu = _batch_blocks(batch_query(frozen, qs, 0.5,
                                    options=QueryOptions(plan="cpu")))
    # pretend the CSR extent overflows the probe's int32 offsets
    monkeypatch.setattr(dp, "_I32_MAX", -1)
    reset_transfer_stats()
    with pytest.raises(dp.DeviceArenaError, match="int32"):
        batch_query(frozen, qs, 0.5, options=QueryOptions(plan="device"))
    st = transfer_stats()
    assert st["arena_uploads"] == 0 and st["batches"] == 0
    assert st["h2d_bytes"] == 0 and st["d2h_bytes"] == 0
    assert getattr(frozen, "_device_arena", None) is None
    assert _batch_blocks(batch_query(
        frozen, qs, 0.5, options=QueryOptions(plan="cpu"))) == cpu


# --------------------------------------------------------------------------
# live delta level: host probe fallback under writes
# --------------------------------------------------------------------------

def test_live_delta_serves_device_plan_via_host_fallback(tmp_path):
    rng = np.random.default_rng(6)
    base = _corpus(rng, n_docs=8)
    scheme = MultisetScheme(seed=13, k=8)
    save_index(IndexBuilder(scheme=scheme).build(base).freeze(),
               tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    delta = [rng.integers(0, 30, 50).astype(np.int64) for _ in range(2)]
    delta.append(base[2].copy())                  # near-dup lands in delta
    for t in delta:
        live.add_text(t)
    assert live.delta.num_texts == len(delta)     # genuinely pre-compaction

    qs = _queries(rng, base) + [delta[-1][:30]]
    oracle = IndexBuilder(scheme=scheme).build(base + delta)
    expected = _batch_blocks(batch_query(oracle, qs, 0.5))
    got = _batch_blocks(live.batch_query(
        qs, 0.5, options=QueryOptions(plan="device")))
    assert got == expected
    # results include hits resolved from the mutable delta level (high
    # text ids), proving the host-probed delta merged into the device scan
    assert any(tid >= len(base) for r in got for tid, _ in r)


# --------------------------------------------------------------------------
# plan resolution: auto downgrade + pin validation
# --------------------------------------------------------------------------

def test_auto_plan_downgrades_without_accelerator():
    xp = resolve_plan(QueryOptions(plan="auto"),
                      capabilities={"device": False})
    assert xp.name == "cpu" and not xp.device
    xp = resolve_plan(QueryOptions(plan="auto"),
                      capabilities={"device": True})
    assert xp.name == "device" and xp.device
    # no capability override: follows the real backend probe, silently
    assert resolve_plan(QueryOptions(plan="auto")).name in ("cpu", "device")


def test_resolved_device_plan_keeps_exact_sketching():
    xp = resolve_plan(QueryOptions(plan="device"))
    assert xp.sketch_backend == "exact"           # bit parity by default
    assert xp.device and xp.fanout == "threaded"


def test_stage_pins_override_plan_defaults():
    # the plan alone chooses probe and sweep: the old probe_backend and
    # sweep pins are unknown options on the wire, in QueryOptions and as
    # keywords of every query entry point
    for name in ("probe_backend", "sweep"):
        with pytest.raises(ValueError, match="unknown query options"):
            QueryOptions.from_dict({"plan": "device", name: "device"})
        with pytest.raises(TypeError):
            QueryOptions(plan="device", **{name: "device"})
    rng = np.random.default_rng(11)
    docs = _corpus(rng)
    frozen = _frozen("multiset", docs)
    live = LiveIndex(frozen=frozen, delta=IndexBuilder(
        scheme=frozen.scheme), doc_map=list(range(len(docs))))
    sharded = ShardedAlignmentIndex(scheme=frozen.scheme, n_shards=2)
    sharded.build(docs)
    al = Aligner.build(docs, similarity="multiset", k=8)
    calls = [lambda **kw: batch_query(frozen, docs[:1], 0.5, **kw),
             lambda **kw: live.batch_query(docs[:1], 0.5, **kw),
             lambda **kw: sharded.batch_query(docs[:1], 0.5, **kw),
             lambda **kw: al.find_batch(docs[:1], 0.5, **kw)]
    for call in calls:
        for name in ("probe_backend", "sweep"):
            with pytest.raises(TypeError, match=name):
                call(**{name: "device"})
    # the plan's own stages resolve from the plan and the two pins left
    xp = resolve_plan(QueryOptions(plan="device", fanout="serial"))
    assert xp.device and xp.fanout == "serial"
    assert xp.sketch_backend == "exact"


def test_unknown_plan_and_invalid_pin_are_errors():
    with pytest.raises(ValueError, match="unknown execution plan"):
        resolve_plan(QueryOptions(plan="gpu"))
    with pytest.raises(TypeError, match="cannot execute"):
        resolve_plan(QueryOptions(plan="cpu", fanout="process"))


# --------------------------------------------------------------------------
# deprecation shims: one release of grace, loudly
# --------------------------------------------------------------------------

def test_legacy_kwargs_warn_name_release_and_round_trip():
    rng = np.random.default_rng(7)
    docs = _corpus(rng)
    frozen = _frozen("multiset", docs)
    qs = _queries(rng, docs)
    new = batch_query(frozen, qs, 0.5,
                      options=QueryOptions(sketch_backend="exact"))
    with pytest.warns(DeprecationWarning, match=r"removed in release 0\.3"):
        old = batch_query(frozen, qs, 0.5,                      # repro: allow[RPR404]
                          sketch_backend="exact")
    assert _batch_blocks(old) == _batch_blocks(new)


def test_aligner_legacy_backend_kwarg_warns_and_matches():
    rng = np.random.default_rng(8)
    docs = _corpus(rng)
    a = Aligner.build(docs, similarity="multiset", k=8)
    qs = _queries(rng, docs)
    new = a.find_batch(qs, 0.5, options=QueryOptions(sketch_backend="exact"))
    with pytest.warns(DeprecationWarning, match=r"options=QueryOptions"):
        old = a.find_batch(qs, 0.5, backend="exact")            # repro: allow[RPR401]
    assert _batch_blocks(old) == _batch_blocks(new)


def test_mixing_options_and_legacy_kwargs_is_an_error():
    rng = np.random.default_rng(9)
    docs = _corpus(rng)
    frozen = _frozen("multiset", docs)
    with pytest.raises(TypeError, match="both"):
        batch_query(frozen, [docs[0][:20]], 0.5,    # repro: allow[RPR404]
                    options=QueryOptions(plan="cpu"), sketch_backend="exact")


# --------------------------------------------------------------------------
# no hidden fallbacks: interpret mode, backend errors, host-swept groups
# --------------------------------------------------------------------------

def test_interpret_decision_has_one_owner(monkeypatch):
    import jax

    from repro.kernels.interpret import resolve_interpret
    assert resolve_interpret() is (jax.default_backend() != "tpu")
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False           # compiled on a TPU
    with pytest.raises(ValueError, match="TPU"):
        resolve_interpret(True)


def test_auto_plan_lets_backend_errors_through(monkeypatch):
    import jax

    def broken():
        raise RuntimeError("backend failed to initialise")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="initialise"):
        resolve_plan(QueryOptions(plan="auto"))


def test_fused_path_gathers_in_int32(monkeypatch):
    # the per-window arrays of a batch are half the bytes of int64 ones:
    # past 32 MiB the allocator maps each afresh on every batch
    seen = {}
    real = Q.sweep_groups

    def spy(g, *rest):
        if isinstance(g.source, dp.DeviceArena):   # the run grouping's
            seen.update(row=g.rows.dtype, cid=g.cids.dtype)
        return real(g, *rest)

    monkeypatch.setattr(Q, "sweep_groups", spy)
    rng = np.random.default_rng(0)
    docs = _corpus(rng)
    frozen = _frozen("multiset", docs)
    qs = _queries(rng, docs)
    got = batch_query(frozen, qs, 0.5, options=QueryOptions(plan="device"))
    assert seen == dict.fromkeys(("row", "cid"), np.dtype(np.int32))
    assert _batch_blocks(got) == _batch_blocks(
        batch_query(frozen, qs, 0.5, options=QueryOptions(plan="cpu")))


@pytest.mark.parametrize("live", [False, True])
def test_device_plan_counts_probes_and_host_swept_groups(live, tmp_path):
    from repro.core.frozen import _concat_ranges
    from repro.core.query import (_SMALL_GROUP_MAX, _group_bounds,
                                  _large_groups_hot, batch_probe)
    rng = np.random.default_rng(10)
    docs = _corpus(rng, n=120)
    frozen = _frozen("multiset", docs)
    index = frozen
    if live:
        save_index(frozen, tmp_path / "idx")
        index = LiveIndex.open(tmp_path / "idx")
    qs = [d[:100].copy() for d in docs[:4]] + _queries(rng, docs)
    m = int(np.ceil(8 * 0.5))
    q, w, c = batch_probe(frozen, frozen.scheme.sketch_batch(qs))
    order, g_lo, g_hi, distinct = _group_bounds(q, w[:, 0], c)
    kept = distinct >= m
    is_large = kept & (g_hi - g_lo > _SMALL_GROUP_MAX)
    large = int(is_large.sum())
    assert large and (kept & (g_hi - g_lo <= _SMALL_GROUP_MAX)).any()
    # the cpu plan's grouping through the test in front of the host sweep
    sizes = (g_hi - g_lo)[is_large]
    at = order[_concat_ranges(g_lo[is_large], sizes)]
    rejected = int((~_large_groups_hot(w[at, 1:5], c[at], sizes, m)).sum())
    reset_transfer_stats()
    opts = QueryOptions(plan="device")
    got = (index.batch_query(qs, 0.5, options=opts) if live
           else batch_query(index, qs, 0.5, options=opts))
    st = transfer_stats()
    assert st["batches"] == 1                     # one resident-arena probe
    assert st["host_large_groups"] == large
    assert 0 <= st["host_large_rejected"] <= st["host_large_groups"]
    assert st["host_large_rejected"] == rejected
    assert st["sweep_launches"] >= 1
    assert _batch_blocks(got) == _batch_blocks(
        batch_query(frozen, qs, 0.5, options=QueryOptions(plan="cpu")))


# --------------------------------------------------------------------------
# one pipeline: live and sharded stores take the run path as well
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["multiset", "tfidf"])
def test_live_store_with_delta_takes_the_run_path(tmp_path, kind):
    rng = np.random.default_rng(12)
    base = _corpus(rng, n_docs=8)
    scheme = SCHEMES[kind](base)
    save_index(IndexBuilder(scheme=scheme).build(base).freeze(),
               tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    delta = [base[3].copy(), rng.integers(0, 30, 50).astype(np.int64)]
    for t in delta:
        live.add_text(t)
    assert live.delta.num_texts == len(delta)
    qs = _queries(rng, base) + [delta[0][:30]]
    cpu = live.batch_query(qs, 0.5, options=QueryOptions(plan="cpu"))
    reset_transfer_stats()
    dev = live.batch_query(qs, 0.5, options=QueryOptions(plan="device"))
    st = transfer_stats()
    assert st["batches"] == 1                     # the frozen level alone
    assert st["probe_text_runs"] > 0              # grouped over runs
    assert _batch_blocks(dev) == _batch_blocks(cpu)
    assert [[a.ncoords for a in r] for r in dev] == \
        [[a.ncoords for a in r] for r in cpu]
    assert any(tid >= len(base) for r in dev for tid, _ in _blocks(r))
    oracle = IndexBuilder(scheme=scheme).build(base + delta)
    assert _batch_blocks(dev) == _batch_blocks(batch_query(oracle, qs, 0.5))


@pytest.mark.parametrize("kind", ["multiset", "tfidf"])
def test_two_shard_store_takes_the_run_path_threaded(kind):
    rng = np.random.default_rng(13)
    docs = _corpus(rng, n_docs=8)
    scheme = SCHEMES[kind](docs)
    sharded = ShardedAlignmentIndex(scheme=scheme, n_shards=2)
    sharded.build(docs).freeze()
    qs = _queries(rng, docs)
    cpu = sharded.batch_query(
        qs, 0.5, options=QueryOptions(plan="cpu", fanout="threaded"))
    reset_transfer_stats()
    dev = sharded.batch_query(
        qs, 0.5, options=QueryOptions(plan="device", fanout="threaded"))
    # counted by the sweep, on the calling thread: each shard's frozen
    # level was grouped over text runs
    assert transfer_stats()["probe_text_runs"] > 0
    assert all(getattr(shard, "_device_arena", None) is not None
               for shard in sharded.shards)
    assert _batch_blocks(dev) == _batch_blocks(cpu)
    assert [[a.ncoords for a in r] for r in dev] == \
        [[a.ncoords for a in r] for r in cpu]
    oracle = IndexBuilder(scheme=scheme).build(docs)
    assert _batch_blocks(dev) == _batch_blocks(batch_query(oracle, qs, 0.5))


def _store(kind, docs, tmp_path):
    scheme = MultisetScheme(seed=13, k=8)
    if kind == "sharded":
        return ShardedAlignmentIndex(scheme=scheme, n_shards=2).build(
            docs).freeze()
    if kind in ("builder", "frozen"):
        builder = IndexBuilder(scheme=scheme).build(docs)
        return builder if kind == "builder" else builder.freeze()
    save_index(IndexBuilder(scheme=scheme).build(docs[:-2]).freeze(),
               tmp_path / "idx")
    live = LiveIndex.open(tmp_path / "idx")
    for t in docs[-2:]:
        live.add_text(t)
    return live


@pytest.mark.parametrize("plan", ["cpu", "device"])
@pytest.mark.parametrize("kind", ["frozen", "builder", "live", "sharded"])
def test_every_store_kind_reaches_the_one_sweep(monkeypatch, tmp_path,
                                                kind, plan):
    rng = np.random.default_rng(14)
    docs = _corpus(rng, n_docs=8)
    store = _store(kind, docs, tmp_path)
    qs = _queries(rng, docs) + [docs[-1][:30]]
    want = _batch_blocks(batch_query(
        IndexBuilder(scheme=MultisetScheme(seed=13, k=8)).build(docs),
        qs, 0.5))
    sources = []
    real = Q.sweep_groups

    def spy(g, *rest):
        sources.append(type(g.source).__name__)
        return real(g, *rest)

    monkeypatch.setattr(Q, "sweep_groups", spy)
    opts = QueryOptions(plan=plan)
    got = (batch_query(store, qs, 0.5, options=opts)
           if kind in ("frozen", "builder")
           else store.batch_query(qs, 0.5, options=opts))
    assert _batch_blocks(got) == want
    # one sweep per level: a live store's frozen base and its delta, each
    # shard of a sharded store; a frozen level's rows stay on the device
    # arena under the device plan
    frozen = "DeviceArena" if plan == "device" else "HostRows"
    assert sources == {"frozen": [frozen], "builder": ["HostRows"],
                       "live": [frozen, "HostRows"],
                       "sharded": [frozen, frozen]}[kind]
