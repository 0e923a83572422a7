#!/usr/bin/env python3
"""Bring-up smoke: the device query plan, served, on one TPU chip.

    python chip_smoke.py [--seed 0] [--tokens 3600000]

One process drives the main path through the entry points a user calls:

1. generate a Zipf-vocabulary corpus from ``--seed`` and build it with
   ``Aligner.build(..., similarity="tfidf", k=16, pipeline="columnar")``
   into a store, sized so that the resident device arena holds at least
   1 GiB (about 1/16 of a v5e chip's HBM);
2. reopen the store with ``Aligner.load(store, mmap=True, live=True)`` and
   upload its arena to the device;
3. serve it through ``repro.serve.AlignServer`` on localhost and send
   64 concurrent ``/query`` requests with
   ``"options": {"plan": "device"}`` at theta 0.8, 64 to 512 tokens long,
   half of them corpus spans with about 10% of their tokens edited;
4. ``/add`` a few documents and query spans of them, so the live delta
   path runs too;
5. run the fused frozen-index path (``repro.core.batch_query``, what a
   non-live server runs) on the same resident arena, and the device sweep
   kernel on seeded rectangle groups of every size bucket (served queries
   of these lengths rarely produce groups small enough to reach it).

Every response must be block-identical to ``find_batch(...,
options=QueryOptions(plan="cpu"))`` on the same store.  Every planted
query must match its source document over the planted span (each plant is
drawn so that the span's sketch estimate reaches theta, which Definition 1
then makes a result).  The arena must upload exactly once, and every
device-plan batch must probe it.

The earlier lines are informational: they are not benchmark numbers.  The
last line is one JSON object with the device.  The script exits non-zero
without that line when JAX finds no TPU, or when any check fails.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

K = 16
THETA = 0.8
VOCAB = 50_000              # a BPE-sized vocabulary
ZIPF_S = 1.1                # token-rank exponent
DOC_LEN = (256, 2048)       # document lengths, uniform
QUERY_LEN = (64, 512)       # query lengths, uniform
EDIT_RATE = 0.10            # share of a planted span's tokens edited
MAX_PLANT_DRAWS = 64        # edit draws per planted query before giving up
QUERIES = 64                # corpus-phase /query requests
WAVE = 16                   # concurrent requests per wave (one batch each)
ADDS = 4                    # /add documents in the live phase
ADD_QUERIES = 8             # planted queries of the added documents
MIN_ARENA_BYTES = 1 << 30   # the arena a full-size run must hold
FULL_TOKENS = 3_600_000     # ~315 arena bytes per corpus token at k=16


def say(*parts) -> None:
    print(*parts, flush=True)


def require_tpu() -> dict:
    """The device JAX reports; exits non-zero unless it is a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        say(f"no TPU: JAX reports platform {dev.platform!r}")
        sys.exit(2)
    return describe_device()


def describe_device() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# --------------------------------------------------------------------------
# data, from the seed
# --------------------------------------------------------------------------


def zipf_probs() -> np.ndarray:
    p = 1.0 / np.arange(1, VOCAB + 1, dtype=np.float64) ** ZIPF_S
    return p / p.sum()


def make_corpus(rng, n_tokens: int, p) -> list[np.ndarray]:
    lens = []
    total = 0
    while total < n_tokens:
        n = int(rng.integers(DOC_LEN[0], DOC_LEN[1] + 1))
        lens.append(n)
        total += n
    toks = rng.choice(VOCAB, size=total, p=p).astype(np.int64)
    return np.split(toks, np.cumsum(lens)[:-1])


def edit(rng, span: np.ndarray, p) -> np.ndarray:
    """About EDIT_RATE of the span's tokens substituted, deleted or
    preceded by an inserted token, one third each."""
    out = list(span)
    n = max(1, round(EDIT_RATE * len(span)))
    pos = np.sort(rng.choice(len(span), size=n, replace=False))[::-1]
    ops = rng.integers(0, 3, size=n)
    new = rng.choice(VOCAB, size=n, p=p)
    for i, op, t in zip(pos, ops, new):
        if op == 0:
            out[i] = int(t)
        elif op == 1:
            del out[i]
        else:
            out.insert(i, int(t))
    return np.asarray(out, np.int64)


def plant(rng, docs: dict, index, p) -> tuple:
    """One planted query: (tokens, doc id, span start, span end, rejected
    draws).  A draw stands when the span's sketch estimate against the
    edited query reaches THETA."""
    from repro.core.query import estimate_similarity
    ids = list(docs)
    for rejected in range(MAX_PLANT_DRAWS):
        doc_id = ids[int(rng.integers(len(ids)))]
        doc = docs[doc_id]
        n = min(int(rng.integers(QUERY_LEN[0], QUERY_LEN[1] + 1)), len(doc))
        s = int(rng.integers(0, len(doc) - n + 1))
        span = doc[s:s + n]
        q = edit(rng, span, p)
        if estimate_similarity(index, q, span) >= THETA:
            return q, doc_id, s, s + n - 1, rejected
    raise AssertionError(f"no plant reached theta in {MAX_PLANT_DRAWS} "
                         "draws")


def make_queries(rng, docs: dict, index, n: int, p, *, planted_share=0.5):
    """``n`` queries, ``planted_share`` of them planted spans, the rest
    fresh Zipf text; shuffled.  Each is (tokens, plant or None) with
    plant = (doc id, span start, span end)."""
    n_plant = round(n * planted_share)
    out, rejected = [], 0
    for _ in range(n_plant):
        q, doc_id, s, e, r = plant(rng, docs, index, p)
        out.append((q, (doc_id, s, e)))
        rejected += r
    for _ in range(n - n_plant):
        m = int(rng.integers(QUERY_LEN[0], QUERY_LEN[1] + 1))
        out.append((rng.choice(VOCAB, size=m, p=p).astype(np.int64), None))
    order = rng.permutation(len(out))
    return [out[i] for i in order], rejected


# --------------------------------------------------------------------------
# checks
# --------------------------------------------------------------------------


def check_parity(responses: list[dict], reference: list) -> None:
    """Each served result equals its ``plan="cpu"`` reference, match for
    match and block for block."""
    for i, (got, want) in enumerate(zip(responses, reference)):
        if got["matches"] != want.to_dict()["matches"]:
            raise AssertionError(f"query {i}: device-plan response differs "
                                 "from the cpu-plan reference")


def check_plants(queries, responses: list[dict]) -> int:
    """Every planted query matches its source over the planted span."""
    n = 0
    for i, ((_, src), got) in enumerate(zip(queries, responses)):
        if src is None:
            continue
        doc_id, s, e = src
        hit = any(m["doc_id"] == doc_id and
                  any(il <= s <= ih and jl <= e <= jh
                      for il, ih, jl, jh in m["blocks"])
                  for m in got["matches"])
        if not hit:
            raise AssertionError(f"query {i}: planted span {s}..{e} of "
                                 f"doc {doc_id} not found")
        n += 1
    return n


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------


async def query_waves(port: int, texts: list) -> tuple[list[dict], list]:
    """Send ``texts`` as /query requests with the device plan, WAVE at a
    time on WAVE keep-alive connections; (results, wave seconds)."""
    from repro.serve.client import AsyncAlignClient
    clients = [await AsyncAlignClient.connect("127.0.0.1", port)
               for _ in range(WAVE)]
    results: list[dict] = []
    waves = []
    try:
        for lo in range(0, len(texts), WAVE):
            chunk = texts[lo:lo + WAVE]
            t0 = time.perf_counter()
            replies = await asyncio.gather(*(
                c.query(t, THETA, options={"plan": "device"})
                for c, t in zip(clients, chunk)))
            waves.append(time.perf_counter() - t0)
            for status, payload in replies:
                if status != 200:
                    raise AssertionError(f"/query answered {status}: "
                                         f"{payload}")
                results.append(payload["result"])
    finally:
        for c in clients:
            await c.close()
    return results, waves


async def serve_phases(aligner, queries, rng, p) -> dict:
    """Phases 3 and 4 against one in-process server; the cpu references
    are computed in-process while the server is idle."""
    from repro.api import QueryOptions
    from repro.serve import AlignServer
    from repro.serve.client import AsyncAlignClient
    cpu = QueryOptions(plan="cpu")
    out = {}
    async with AlignServer(aligner, host="127.0.0.1", port=0,
                           max_batch=32) as server:
        texts = [q for q, _ in queries]
        got, waves = await query_waves(server.port, texts)
        check_parity(got, aligner.find_batch(texts, THETA, options=cpu))
        out["planted"] = check_plants(queries, got)
        out["waves"] = waves

        # live phase: /add documents, then query spans of them
        writer = await AsyncAlignClient.connect("127.0.0.1", server.port)
        try:
            added = {}
            for _ in range(ADDS):
                n = int(rng.integers(DOC_LEN[0], DOC_LEN[1] + 1))
                doc = rng.choice(VOCAB, size=n, p=p).astype(np.int64)
                added[await writer.add(doc)] = doc
        finally:
            await writer.close()
        live_q, _ = make_queries(rng, added, aligner._index, ADD_QUERIES, p,
                                 planted_share=1.0)
        live_texts = [q for q, _ in live_q]
        live_got, _ = await query_waves(server.port, live_texts)
        check_parity(live_got,
                     aligner.find_batch(live_texts, THETA, options=cpu))
        out["planted_live"] = check_plants(live_q, live_got)
        out["responses"] = len(got) + len(live_got)
        out["metrics"] = server.metrics.snapshot()
    return out


def fused_phase(frozen, texts) -> int:
    """The fused frozen-index path on the resident arena, against the cpu
    plan on the same SearchIndex; returns the number of queries."""
    from repro.api import QueryOptions
    from repro.core import batch_query

    def blocks(plan):
        res = batch_query(frozen, texts, THETA,
                          options=QueryOptions(plan=plan))
        return [[(a.text_id, a.blocks, a.ncoords) for a in r] for r in res]

    if blocks("device") != blocks("cpu"):
        raise AssertionError("fused device path differs from the cpu plan")
    return len(texts)


def sweep_kernel_phase(rng, groups: int = 64) -> int:
    """The device sweep kernel against the host grouped sweep on
    ``groups`` seeded rectangle groups per size bucket.  Served traffic
    rarely reaches the kernel: its matched (query, text) groups mostly
    hold more than 32 windows and are swept on the host.  Returns the
    number of groups compared."""
    from repro.core.query import (_SIZE_BUCKETS, _extract_runs,
                                  _sweep_small_batch)
    from repro.kernels.sweep_grid import sweep_small_batch_device
    n = 0
    for lo, hi in _SIZE_BUCKETS:
        sizes = rng.integers(lo + 1, hi + 1, size=groups)
        base = rng.integers(0, 4096, size=(groups, 1, 2))
        start = base + rng.integers(0, 12, size=(groups, hi, 2))
        end = start + rng.integers(0, 24, size=(groups, hi, 2))
        arr = np.stack([start[..., 0], end[..., 0], start[..., 1],
                        end[..., 1]], axis=-1)
        m = max(1, hi // 4)
        want = _sweep_small_batch(arr, sizes, m)
        if _extract_runs(*sweep_small_batch_device(arr, sizes, m)) != want:
            raise AssertionError(f"device sweep differs from the host "
                                 f"sweep at S={hi}")
        n += groups
    return n


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------


class CompileCounter:
    """Backend compiles (count, seconds) seen by JAX's monitoring hook
    while the ``with`` block runs."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self) -> "CompileCounter":
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration


def run(args, device: dict) -> dict:
    from repro.compile_cache import configure_compile_cache
    say(f"compile cache: {configure_compile_cache()}")
    with CompileCounter() as compiles:
        return _run(args, device, compiles)


def _run(args, device: dict, compiles: CompileCounter) -> dict:
    import jax

    from repro.api import Aligner
    from repro.core.device_plan import (device_arena, reset_transfer_stats,
                                        transfer_stats)

    rng = np.random.default_rng(args.seed)
    p = zipf_probs()
    docs = make_corpus(rng, args.tokens, p)
    n_tokens = int(sum(len(d) for d in docs))
    if args.tokens < FULL_TOKENS:
        say(f"corpus cut: {n_tokens} tokens, not {FULL_TOKENS}, as "
            "--tokens asks; the 1 GiB arena check is skipped")

    store = Path(args.workdir) / "store"
    t0 = time.perf_counter()
    Aligner.build(docs, similarity="tfidf", k=K, pipeline="columnar",
                  store=str(store))
    build_s = time.perf_counter() - t0
    aligner = Aligner.load(str(store), mmap=True, live=True)
    say(f"build s: {build_s} ({len(docs)} docs, {n_tokens} tokens, "
        f"{aligner.num_windows} windows)")

    reset_transfer_stats()
    frozen = aligner._index.frozen
    t0 = time.perf_counter()
    da = device_arena(frozen)
    jax.block_until_ready([da.khi, da.klo, da.ktag, da.offsets, da.win_rect])
    upload_s = time.perf_counter() - t0
    say(f"arena bytes: {da.nbytes} ({da.n} slots, mode {da.mode})")
    say(f"upload s: {upload_s}")
    if args.tokens >= FULL_TOKENS and da.nbytes < MIN_ARENA_BYTES:
        raise AssertionError(f"arena holds {da.nbytes} bytes, under "
                             f"{MIN_ARENA_BYTES}")

    queries, rejected = make_queries(
        rng, dict(enumerate(docs)), aligner._index, QUERIES, p)
    say(f"plant draws rejected by the sketch estimate: {rejected}")
    served = asyncio.run(serve_phases(aligner, queries, rng, p))
    fused = fused_phase(frozen, [q for q, _ in queries])
    swept = sweep_kernel_phase(rng)

    counters = served["metrics"]["counters"]
    stats = transfer_stats()
    waves = served["waves"]
    say(f"first wave s (incl. compile): {waves[0]}")
    say(f"median later wave s: "
        f"{statistics.median(waves[1:]) if len(waves) > 1 else 'n/a'}")
    say(f"device-plan responses identical to plan=cpu: "
        f"{served['responses']} (planted matched: {served['planted']} "
        f"corpus, {served['planted_live']} added)")
    say(f"fused frozen-path queries identical to plan=cpu: {fused}")
    say(f"sweep kernel groups identical to the host sweep: {swept}")
    say(f"server batches: {counters['batches_total']}, errors: "
        f"{counters['errors_total']}")
    say(f"server stage seconds: "
        f"{json.dumps(served['metrics']['stage_seconds'])}")
    say(f"transfer stats: {json.dumps(stats)}")
    say(f"large groups swept on the host: {stats['host_large_groups']}")
    say(f"backend compiles: {compiles.n} ({compiles.seconds} s)")
    mem = jax.devices()[0].memory_stats() or {}
    say(f"peak bytes in use: {mem.get('peak_bytes_in_use', 'not reported')}")

    if counters["errors_total"]:
        raise AssertionError(f"{counters['errors_total']} server errors")
    if stats["arena_uploads"] != 1:
        raise AssertionError(f"arena uploaded {stats['arena_uploads']} "
                             "times, not once")
    # one resident-arena probe per device-plan batch: each server batch
    # and the fused phase's one device batch
    if stats["batches"] != counters["batches_total"] + 1:
        raise AssertionError(
            f"{stats['batches']} device probes for "
            f"{counters['batches_total']} server batches + 1 fused batch: "
            "a batch did not probe the resident arena")
    return {"ok": True, "device": device}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokens", type=int, default=FULL_TOKENS,
                    help="corpus tokens (default: a >= 1 GiB arena)")
    ap.add_argument("--workdir", default=str(ROOT / ".chip_smoke"),
                    help="scratch directory for the store, emptied before "
                         "and removed after the run (default: .chip_smoke "
                         "in the checkout)")
    args = ap.parse_args(argv)

    device = require_tpu()
    import jax
    say(f"device kind: {device['kind']}")
    say(f"device count: {device['count']}")
    say(f"jax version: {jax.__version__}")
    shutil.rmtree(args.workdir, ignore_errors=True)
    try:
        result = run(args, device)
    finally:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
