"""§6 query-latency study: latency vs corpus size and threshold θ, plus
end-to-end recall of planted near-duplicates (the accuracy-guarantee side:
every subsequence with estimated Jaccard >= θ must be returned).

Also benchmarks the serving-side index layouts: frozen CSR arrays vs the
mutable dict-of-lists build layout (resident bytes + single-query latency),
the batched query engine (`batch_query`) vs a per-query loop across batch
sizes — the MONO headline claims (index size, query throughput) — and the
fused probe arena: a B ∈ {1, 16, 64, 256} sweep of the one-shot arena
probe of a frozen index against the per-coordinate dict probe of the same
corpus unfrozen (both through the one batch pipeline), a cpu-vs-device
plan row, a serial-vs-threaded sharded fan-out row, and a
Zipf-distributed query workload row (the ROADMAP warm-path study).
"""

from __future__ import annotations

import numpy as np

import tempfile
from pathlib import Path

from repro.core import IndexBuilder, QueryOptions, SearchIndex, \
    ShardedAlignmentIndex, batch_query, make_scheme, query

from .common import print_table, save_result, timed, zipf_text


def _blocks(results):
    return [(a.text_id, a.blocks) for a in results]


def _dup_corpus(rng, n_docs, doc_len, n_pass, pass_len):
    """Distinctive (large-vocab) docs, each carrying one planted duplicate
    passage — the near-duplicate serving regime: queries hit a handful of
    texts with small (query, text) window groups."""
    passages = [rng.integers(0, 1 << 20, size=pass_len).astype(np.int64)
                for _ in range(n_pass)]
    docs = []
    for i in range(n_docs):
        base = rng.integers(0, 1 << 20, size=doc_len).astype(np.int64)
        o = int(rng.integers(0, doc_len - pass_len))
        base[o:o + pass_len] = passages[i % n_pass]
        docs.append(base)
    return passages, docs


def _passage_queries(rng, passages, n, q_len=90):
    pass_len = len(passages[0])
    out = []
    for _ in range(n):
        p = passages[int(rng.integers(0, len(passages)))]
        o = int(rng.integers(0, pass_len - q_len))
        out.append(p[o:o + q_len].copy())
    return out


def run(quick: bool = True) -> dict:
    rows_sz, rows_theta = [], []
    k = 8
    sizes = [4, 16] if quick else [4, 16, 64]
    for n_docs in sizes:
        scheme = make_scheme("multiset", seed=31, k=k)
        docs = [zipf_text(1200, seed=300 + i) for i in range(n_docs)]
        idx = IndexBuilder(scheme=scheme).build(docs)
        qtext = docs[0][100:220].copy()
        res, t = timed(lambda: query(idx, qtext, 0.6), repeat=3)
        rows_sz.append({"docs": n_docs, "windows": idx.num_windows,
                        "query_s": t, "hits": len(res)})

    scheme = make_scheme("multiset", seed=32, k=k)
    docs = [zipf_text(1500, seed=400 + i) for i in range(8)]
    idx = IndexBuilder(scheme=scheme).build(docs)
    qtext = docs[3][200:320].copy()
    for theta in (0.3, 0.6, 0.9):
        res, t = timed(lambda: query(idx, qtext, theta), repeat=3)
        rows_theta.append({"theta": theta, "query_s": t,
                           "result_cells": sum(a.num_cells for a in res)})

    # recall of a planted exact sub-duplicate at theta=0.9
    found = any(a.text_id == 3 for a in query(idx, qtext, 0.9))

    # ---- frozen CSR layout vs dict layout + batched query engine ----------
    # serving configuration: the paper's default sketch width (k = 16)
    scheme = make_scheme("multiset", seed=33, k=16)
    n_docs = 24 if quick else 64
    docs = [zipf_text(900, seed=500 + i) for i in range(n_docs)]
    dict_idx = IndexBuilder(scheme=scheme).build(docs)
    frozen_idx = dict_idx.freeze()
    dict_bytes, frozen_bytes = dict_idx.nbytes(), frozen_idx.nbytes()

    theta = 0.6
    rng = np.random.default_rng(7)

    def make_queries(n):
        offs = rng.integers(0, 700, size=n)
        return [docs[i % n_docs][int(o):int(o) + 120].copy()
                for i, o in enumerate(offs)]

    q1 = make_queries(1)[0]
    _, t_dict = timed(lambda: query(dict_idx, q1, theta), repeat=3)
    _, t_frozen = timed(lambda: query(frozen_idx, q1, theta), repeat=3)
    rows_frozen = [
        {"layout": "dict", "index_MB": dict_bytes / 1e6, "query_s": t_dict},
        {"layout": "frozen_csr", "index_MB": frozen_bytes / 1e6,
         "query_s": t_frozen},
    ]

    # save -> mmap-load -> query: the versioned-store serving path (PR 2);
    # arrays stay on disk and page in through the OS cache
    with tempfile.TemporaryDirectory() as tmp:
        store = str(Path(tmp) / "idx")
        _, t_save = timed(lambda: frozen_idx.save(store))
        mmap_idx, t_load = timed(lambda: SearchIndex.load(store, mmap=True))
        mmap_res, t_mmap = timed(lambda: query(mmap_idx, q1, theta), repeat=3)
        mmap_equal = _blocks(mmap_res) == _blocks(query(frozen_idx, q1, theta))
        rows_frozen.append({"layout": "mmap_store",
                            "index_MB": frozen_bytes / 1e6,
                            "query_s": t_mmap})
        rows_mmap = [{"save_s": t_save, "load_s": t_load, "query_s": t_mmap,
                      "mmap_backed": mmap_idx.is_mmap(),
                      "equal": mmap_equal}]

    batch_sizes = [1, 4, 16] if quick else [1, 4, 16, 64]
    rows_batch, speedup_at, equal_all = [], {}, True
    for bs in batch_sizes:
        qs = make_queries(bs)
        loop_res, t_loop = timed(
            lambda: [query(dict_idx, q, theta) for q in qs], repeat=2)
        bat_res, t_bat = timed(
            lambda: batch_query(frozen_idx, qs, theta), repeat=2)
        equal = [_blocks(r) for r in loop_res] == [_blocks(r) for r in bat_res]
        equal_all = equal_all and equal
        speedup_at[bs] = t_loop / t_bat
        rows_batch.append({"batch": bs, "looped_s": t_loop,
                           "batched_s": t_bat, "speedup": t_loop / t_bat,
                           "batched_qps": bs / t_bat, "equal": equal})

    # ---- fused probe arena vs the per-coordinate dict probe -------------
    # near-duplicate serving workload: many short distinctive docs, queries
    # hitting the planted duplicates with small window groups (the regime
    # the grouped small-sweep dispatcher and one-shot probe target).  The
    # same corpus unfrozen is probed coordinate by coordinate through its
    # dict tables; the device plan's resident probe gives the parity row
    rng2 = np.random.default_rng(11)
    k2, theta2 = 16, 0.5
    n_docs2, doc_len2 = (96, 200) if quick else (240, 320)
    n_pass2, pass_len2 = (16, 110) if quick else (40, 160)
    passages, dup_docs = _dup_corpus(rng2, n_docs2, doc_len2, n_pass2,
                                     pass_len2)
    scheme2 = make_scheme("multiset", seed=35, k=k2)
    dict_idx = IndexBuilder(scheme=scheme2).build(dup_docs)
    arena_idx = dict_idx.freeze()
    rows_arena, arena_speedup_at, arena_equal = [], {}, True
    for bs in (1, 16, 64, 256):
        qs = _passage_queries(rng2, passages, bs)
        sk = scheme2.sketch_batch(qs)   # shared: isolate the probe + sweep
        dict_res, t_dict = timed(
            lambda: batch_query(dict_idx, qs, theta2,
                                options=QueryOptions(sketches=sk)),
            repeat=3)
        new_res, t_new = timed(
            lambda: batch_query(arena_idx, qs, theta2,
                                options=QueryOptions(sketches=sk)),
            repeat=3)
        equal = [_blocks(r) for r in dict_res] == \
            [_blocks(r) for r in new_res]
        if bs == 16:   # resident-probe parity datapoint (interpret mode)
            dev_res = batch_query(arena_idx, qs, theta2,
                                  options=QueryOptions(plan="device",
                                                       sketches=sk))
            equal = equal and \
                [_blocks(r) for r in dev_res] == [_blocks(r) for r in new_res]
        arena_equal = arena_equal and equal
        arena_speedup_at[bs] = t_dict / t_new
        rows_arena.append({"batch": bs, "dict_probe_s": t_dict,
                           "arena_s": t_new, "speedup": t_dict / t_new,
                           "arena_qps": bs / t_new, "equal": equal})

    # ---- execution plans: cpu pipeline vs fused device pipeline ----------
    # plan="device" keeps the arena resident, probes + sweeps on-device
    # (interpret mode off-TPU) and must stay block-for-block identical to
    # plan="cpu"; the sweep also records the residency soak (arena uploads
    # across batches must not grow)
    from repro.core.device_plan import reset_transfer_stats, transfer_stats
    rows_plan, plan_equal = [], True
    reset_transfer_stats()
    for bs in (16, 64):
        qs = _passage_queries(rng2, passages, bs)
        sk = scheme2.sketch_batch(qs)
        cpu_res, t_cpu = timed(
            lambda: batch_query(arena_idx, qs, theta2,
                                options=QueryOptions(plan="cpu",
                                                     sketches=sk)),
            repeat=3)
        dev_res, t_dev = timed(
            lambda: batch_query(arena_idx, qs, theta2,
                                options=QueryOptions(plan="device",
                                                     sketches=sk)),
            repeat=3)
        equal = [_blocks(r) for r in cpu_res] == [_blocks(r) for r in dev_res]
        plan_equal = plan_equal and equal
        rows_plan.append({"batch": bs, "cpu_s": t_cpu, "device_s": t_dev,
                          "device_qps": bs / t_dev, "equal": equal})
    plan_soak = transfer_stats()

    # ---- sharded fan-out: serial loop vs thread-pool overlap --------------
    # sketches are computed once and shared by both paths (and by every
    # shard), so the row isolates the per-shard probe + sweep fan-out
    n_shards = 4
    fanout_B = 256
    sharded = ShardedAlignmentIndex(scheme=scheme2, n_shards=n_shards)
    sharded.build(dup_docs).freeze()
    fan_qs = _passage_queries(rng2, passages, fanout_B)
    fan_sk = scheme2.sketch_batch(fan_qs)
    # warm-up: builds the per-shard arenas and the fan-out thread pool so
    # neither timed path pays one-time setup
    sharded.batch_query(fan_qs[:8], theta2,
                        options=QueryOptions(sketches=fan_sk[:8]))
    ser_res, t_serial = timed(
        lambda: sharded.batch_query(
            fan_qs, theta2,
            options=QueryOptions(sketches=fan_sk, fanout="serial")),
        repeat=5)
    thr_res, t_threaded = timed(
        lambda: sharded.batch_query(
            fan_qs, theta2,
            options=QueryOptions(sketches=fan_sk, fanout="threaded")),
        repeat=5)
    fanout_equal = [_blocks(r) for r in ser_res] == \
        [_blocks(r) for r in thr_res]
    rows_fanout = [{"fanout": "serial", "shards": n_shards,
                    "batch": fanout_B, "batch_s": t_serial,
                    "qps": fanout_B / t_serial},
                   {"fanout": "threaded", "shards": n_shards,
                    "batch": fanout_B, "batch_s": t_threaded,
                    "qps": fanout_B / t_threaded}]

    # ---- Zipf-distributed query traffic (warm-path / mmap eviction study) -
    # a small popular set dominates: repeated probes re-touch the same arena
    # pages (page-cache warm path) vs a uniform spread of the pool
    pool = _passage_queries(rng2, passages, 32)
    zipf_B = 128 if quick else 512
    ranks = np.minimum(rng2.zipf(1.2, size=zipf_B) - 1, len(pool) - 1)
    zipf_qs = [pool[int(r)] for r in ranks]
    uni_qs = [pool[i % len(pool)] for i in range(zipf_B)]
    zsk = scheme2.sketch_batch(zipf_qs)
    usk = scheme2.sketch_batch(uni_qs)
    _, t_zipf = timed(lambda: batch_query(arena_idx, zipf_qs, theta2,
                                          options=QueryOptions(sketches=zsk)),
                      repeat=3)
    _, t_uni = timed(lambda: batch_query(arena_idx, uni_qs, theta2,
                                         options=QueryOptions(sketches=usk)),
                     repeat=3)
    rows_zipf = [{"workload": "zipf(1.2)", "batch": zipf_B,
                  "distinct_queries": int(len(np.unique(ranks))),
                  "batch_s": t_zipf, "qps": zipf_B / t_zipf},
                 {"workload": "uniform", "batch": zipf_B,
                  "distinct_queries": len(pool),
                  "batch_s": t_uni, "qps": zipf_B / t_uni}]

    print_table("query latency vs corpus size (theta=0.6)", rows_sz)
    print_table("query latency vs theta", rows_theta)
    print_table("index layout: dict vs frozen CSR vs mmap store", rows_frozen)
    print_table("save -> mmap-load -> query (versioned store)", rows_mmap)
    print_table("batched query engine vs per-query loop (theta=0.6)",
                rows_batch)
    print_table("probe arena vs per-coordinate dict probes (theta=0.5)",
                rows_arena)
    print_table("execution plans: cpu vs fused device (theta=0.5)",
                rows_plan)
    print_table(f"sharded fan-out: serial vs threaded (B={fanout_B})",
                rows_fanout)
    print_table("Zipf vs uniform query traffic (probe arena)", rows_zipf)
    claims = {
        "planted_dup_found_at_high_theta": bool(found),
        "results_monotone_in_theta": all(
            rows_theta[i]["result_cells"] >= rows_theta[i + 1]["result_cells"]
            for i in range(len(rows_theta) - 1)),
        "frozen_index_smaller_than_dict": frozen_bytes < dict_bytes,
        "batched_equals_looped": bool(equal_all),
        "batched_speedup_ge_3x_at_16": speedup_at[16] >= 3.0,
        "mmap_store_serves_identically": bool(mmap_equal)
        and bool(rows_mmap[0]["mmap_backed"]),
        "probe_arena_equals_dict_probe_and_device_plan": bool(arena_equal),
        # device pipeline parity is bit-exact by construction (host f64
        # sketch + integer-exact kernels); residency means the arena
        # crossed the bus at most once across the whole multi-batch sweep
        "device_plan_equals_cpu": bool(plan_equal),
        "device_arena_uploaded_once": plan_soak["arena_uploads"] <= 1
        and plan_soak["batches"] >= 2,
        # parity on small 2-core CI runners; the overlap win needs real
        # cores / cold mmap pages.  The gate exists to catch pathological
        # contention (a GIL-convoyed sweep measured 2.2x serial), so the
        # slack is sized for noisy shared runners, not for 5% wins
        "threaded_fanout_no_worse": bool(fanout_equal)
        and t_threaded <= t_serial * 1.25,
    }
    rec = {"vs_size": rows_sz, "vs_theta": rows_theta,
           "layouts": rows_frozen, "mmap_store": rows_mmap,
           "batched": rows_batch, "probe_arena": rows_arena,
           "probe_arena_speedup": arena_speedup_at,
           "execution_plans": rows_plan, "device_plan_soak": plan_soak,
           "sharded_fanout": rows_fanout, "zipf_traffic": rows_zipf,
           "claims": claims}
    save_result("query", rec)
    return rec
