"""Serving front-end integration tests: dynamic batching, deadlines,
backpressure, mid-flight compaction, and the wire protocol.

No pytest-asyncio: every async test drives its own loop via
``asyncio.run``.  The server binds port 0 (ephemeral) on 127.0.0.1.
"""

from __future__ import annotations

import asyncio
import math
import threading
import time

import numpy as np
import pytest

from repro.api import Aligner, Match, QueryOptions, QueryResult
from repro.serve import AlignServer, DynamicBatcher, QueueFull
from repro.serve.batcher import DeadlineExceeded
from repro.serve.client import (AlignClient, AsyncAlignClient, AsyncWSClient,
                                ServerError)


def _mk_aligner(n_docs: int = 30, doc_len: int = 120, live: bool = False,
                tmp_path=None):
    rng = np.random.default_rng(5)
    docs = [rng.integers(0, 1 << 40, size=doc_len) for _ in range(n_docs)]
    if live:
        store = str(tmp_path / "idx")
        Aligner.build(docs, similarity="multiset", seed=3, k=8,
                      pipeline="columnar", store=store)
        return Aligner.load(store, live=True), docs
    return Aligner.build(docs, similarity="multiset", seed=3, k=8), docs


class _ThreadServer:
    """Run an AlignServer on a background event loop so blocking clients
    (http.client) can talk to it from the test thread."""

    def __init__(self, aligner, **kw):
        self.aligner = aligner
        self.kw = kw
        self.server = None
        self.loop = None

    def __enter__(self):
        started = threading.Event()

        def run():
            self.loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self.loop)
            self.server = self.loop.run_until_complete(
                AlignServer(self.aligner, **self.kw).start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        assert started.wait(10)
        return self.server

    def __exit__(self, *exc):
        asyncio.run_coroutine_threadsafe(
            self.server.close(), self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)


def test_http_query_roundtrip_typed_results(tmp_path):
    aligner, docs = _mk_aligner()
    with _ThreadServer(aligner) as srv:
        with AlignClient(port=srv.port) as client:
            # a snippet of doc 7 must come back as a typed match on doc 7
            snippet = [int(t) for t in docs[7][10:90]]
            result = QueryResult.from_dict(client.query(snippet, 0.5))
            assert result, "planted snippet found nothing"
            assert any(m.doc_id == 7 for m in result)
            for m in result.matches:
                assert isinstance(m, Match)
                assert m.estimated_similarity >= 0.5
                assert m.span[0] <= m.span[1]
            # novel text: clean empty result, not an error
            novel = [int(t) for t in
                     np.random.default_rng(9).integers(0, 1 << 40, 80)]
            assert QueryResult.from_dict(client.query(novel, 0.5)).matches \
                == []
            health = client.healthz()
            assert health["docs"] == len(docs)
            snap = client.metrics()
            assert snap["counters"]["requests_total"] == 2
            assert snap["counters"]["responses_total"] == 2
            assert snap["counters"]["errors_total"] == 0


def test_http_error_statuses(tmp_path):
    aligner, _ = _mk_aligner(n_docs=4)
    aligner.freeze()            # frozen, not live: /add must 409
    with _ThreadServer(aligner) as srv:
        with AlignClient(port=srv.port) as client:
            with pytest.raises(ServerError) as ei:
                client.query([1, 2, 3], theta=7.5)      # theta out of range
            assert ei.value.status == 400
            status, _ = client._request("POST", "/nope", {})
            assert status == 404
            status, _ = client._request("GET", "/query")
            assert status == 405
            # /add against a non-live (fully frozen) aligner is a 409
            with pytest.raises(ServerError) as ei:
                client.add([1, 2, 3])
            assert ei.value.status == 409


def test_batcher_coalesces_concurrent_requests():
    """N concurrent same-key queries must cost <= ceil(N/max_batch)
    find_batch probes — the tentpole's coalescing contract."""
    aligner, docs = _mk_aligner()
    probes = []
    orig = aligner.find_batch

    def counting(texts, theta, **kw):
        probes.append(len(texts))
        return orig(texts, theta, **kw)

    aligner.find_batch = counting
    N, max_batch = 24, 8

    async def main():
        batcher = DynamicBatcher(aligner, max_batch=max_batch,
                                 max_linger_us=50_000.0)
        # all N submitted before the drain task first runs -> the queue
        # already holds every request when batching starts
        futs = [batcher.submit_query([int(t) for t in docs[i % 5][:60]], 0.5)
                for i in range(N)]
        results = await asyncio.gather(*futs)
        await batcher.close()
        return results, batcher.metrics.snapshot()

    results, snap = asyncio.run(main())
    assert len(results) == N
    assert all(isinstance(r, QueryResult) for r in results)
    assert len(probes) <= math.ceil(N / max_batch)
    assert all(p <= max_batch for p in probes)
    assert snap["counters"]["batches_total"] == len(probes)
    assert snap["batch_size"]["count"] == len(probes)


def test_batcher_splits_incompatible_options():
    """Different (theta, options) keys may not share a find_batch call."""
    aligner, docs = _mk_aligner()
    seen = []
    orig = aligner.find_batch

    def spy(texts, theta, *, options=None, **kw):
        seen.append((theta, options.batch_key()))
        return orig(texts, theta, options=options, **kw)

    aligner.find_batch = spy

    async def main():
        batcher = DynamicBatcher(aligner, max_batch=32,
                                 max_linger_us=50_000.0)
        q = [int(t) for t in docs[0][:60]]
        futs = [batcher.submit_query(q, 0.5),
                batcher.submit_query(q, 0.8),
                batcher.submit_query(q, 0.5,
                                     options=QueryOptions(
                                         sketch_backend="exact"))]
        await asyncio.gather(*futs)
        await batcher.close()

    asyncio.run(main())
    assert len(seen) == 3
    assert len(set(seen)) == 3


def test_execution_plan_rides_the_wire_and_keys_batches():
    """``options.plan`` survives the request envelope and partitions the
    batcher's coalescing key, so mixed-plan traffic never shares a
    ``find_batch`` call (a cpu request must not ride a device batch)."""
    from repro.serve.protocol import ProtocolError, parse_query_request
    req = parse_query_request(
        {"text": [1, 2, 3], "theta": 0.6, "options": {"plan": "device"}})
    assert req.options.plan == "device"
    assert req.options.batch_key() != QueryOptions().batch_key()
    # same plan, same pins -> same key: coalescable
    assert req.options.batch_key() == \
        QueryOptions(plan="device").batch_key()
    # server-side sketching means client-supplied sketches stay rejected
    with pytest.raises(ProtocolError, match="sketches"):
        parse_query_request({"text": [1], "options": {"sketches": []}})

    aligner, docs = _mk_aligner()
    seen = []
    orig = aligner.find_batch

    def spy(texts, theta, *, options=None, **kw):
        seen.append(options.batch_key())
        return orig(texts, theta, options=options, **kw)

    aligner.find_batch = spy

    async def main():
        batcher = DynamicBatcher(aligner, max_batch=32,
                                 max_linger_us=50_000.0)
        q = [int(t) for t in docs[0][:60]]
        futs = [batcher.submit_query(q, 0.5),
                batcher.submit_query(q, 0.5),
                batcher.submit_query(q, 0.5,
                                     options=QueryOptions(plan="device"))]
        res = await asyncio.gather(*futs)
        await batcher.close()
        return res

    res = asyncio.run(main())
    assert len(seen) == 2                 # 2 cpu coalesced + 1 device
    assert len(set(seen)) == 2
    # and the device-plan result matches the coalesced cpu results
    assert res[2].to_dict() == res[0].to_dict()


def test_deadline_expired_skips_probe():
    """A request whose deadline passes while queued is failed with
    DeadlineExceeded and must never reach the engine."""
    aligner, docs = _mk_aligner()
    probes = []
    orig = aligner.find_batch

    def counting(texts, theta, **kw):
        probes.append(len(texts))
        return orig(texts, theta, **kw)

    aligner.find_batch = counting

    async def main():
        batcher = DynamicBatcher(aligner, max_batch=4, max_linger_us=100.0)
        # park the engine so the query's 20 ms deadline expires in-queue
        batcher.submit_control(lambda: time.sleep(0.2), label="stall")
        fut = batcher.submit_query([int(t) for t in docs[0][:60]], 0.5,
                                   deadline_s=0.02)
        with pytest.raises(DeadlineExceeded):
            await fut
        snap = batcher.metrics.snapshot()
        await batcher.close()
        return snap

    snap = asyncio.run(main())
    assert probes == []
    assert snap["counters"]["expired_total"] == 1
    assert snap["counters"]["batches_total"] == 0


def test_deadline_maps_to_504():
    aligner, docs = _mk_aligner()

    async def main():
        async with AlignServer(aligner, max_linger_us=100.0) as srv:
            # engine parked -> the 10 ms deadline cannot be met
            srv.batcher.submit_control(lambda: time.sleep(0.2),
                                       label="stall")
            client = await AsyncAlignClient.connect("127.0.0.1", srv.port)
            status, payload = await client.query(
                [int(t) for t in docs[0][:60]], 0.5, deadline_ms=10)
            await client.close()
            return status, payload

    status, payload = asyncio.run(main())
    assert status == 504
    assert payload["ok"] is False


def test_backpressure_503_at_queue_cap():
    aligner, docs = _mk_aligner()

    async def main():
        async with AlignServer(aligner, queue_cap=3,
                               max_linger_us=100.0) as srv:
            srv.batcher.submit_control(lambda: time.sleep(0.3),
                                       label="stall")
            ws = await AsyncWSClient.connect("127.0.0.1", srv.port)
            q = [int(t) for t in docs[0][:60]]
            futs = [ws.submit(q, 0.5) for _ in range(5)]
            msgs = await asyncio.gather(*futs)
            snap = srv.metrics.snapshot()
            await ws.close()
            return msgs, snap

    msgs, snap = asyncio.run(main())
    rejected = [m for m in msgs if not m.get("ok", False)]
    served = [m for m in msgs if m.get("ok", False)]
    assert len(served) == 3 and len(rejected) == 2
    assert all(m["status"] == 503 for m in rejected)
    assert snap["counters"]["rejected_total"] == 2
    # admission frees as requests complete: the server is not wedged
    aligner2_check = served[0]["result"]
    assert "matches" in aligner2_check


def test_ws_pipelining_correlates_by_id():
    aligner, docs = _mk_aligner()

    async def main():
        async with AlignServer(aligner, max_linger_us=20_000.0) as srv:
            ws = await AsyncWSClient.connect("127.0.0.1", srv.port)
            futs = {i: ws.submit([int(t) for t in docs[i][:60]], 0.5)
                    for i in range(8)}
            msgs = {i: await f for i, f in futs.items()}
            await ws.close()
            return msgs

    msgs = asyncio.run(main())
    for i, msg in msgs.items():
        assert msg["ok"], msg
        res = QueryResult.from_dict(msg["result"])
        # each doc's own prefix must find that doc (self-hit)
        assert any(m.doc_id == i for m in res), (i, res.matches)


def test_add_is_read_your_writes(tmp_path):
    aligner, docs = _mk_aligner(live=True, tmp_path=tmp_path)
    new_doc = [int(t) for t in
               np.random.default_rng(11).integers(0, 1 << 40, 120)]

    async def main():
        async with AlignServer(aligner) as srv:
            client = await AsyncAlignClient.connect("127.0.0.1", srv.port)
            doc_id = await client.add(new_doc)
            # enqueued after the add -> FIFO guarantees visibility
            status, payload = await client.query(new_doc[20:100], 0.5)
            await client.close()
            return doc_id, status, payload

    doc_id, status, payload = asyncio.run(main())
    assert doc_id == len(docs)
    assert status == 200
    res = QueryResult.from_dict(payload["result"])
    assert any(m.doc_id == doc_id for m in res)


def test_midflight_compaction_bit_identical(tmp_path):
    """Queries racing a /compact (seal -> off-thread merge -> promote)
    must answer bit-identically to the quiesced server, with the
    generation bumped and zero errors."""
    aligner, docs = _mk_aligner(n_docs=40, live=True, tmp_path=tmp_path)
    rng = np.random.default_rng(6)
    delta = [rng.integers(0, 1 << 40, size=120) for _ in range(8)]
    queries = [[int(t) for t in d[10:90]] for d in docs[:6] + delta[:4]]

    async def main():
        async with AlignServer(aligner, max_linger_us=500.0) as srv:
            ctl = await AsyncAlignClient.connect("127.0.0.1", srv.port)
            for d in delta:
                await ctl.add([int(t) for t in d])
            ws = await AsyncWSClient.connect("127.0.0.1", srv.port)
            gen0 = (await ctl.request("GET", "/healthz"))[1]["generation"]

            answers = []

            async def traffic():
                for round_ in range(12):
                    futs = [ws.submit(q, 0.5) for q in queries]
                    answers.extend(await asyncio.gather(*futs))
                    await asyncio.sleep(0)

            compact_task = asyncio.ensure_future(ctl.compact())
            await traffic()
            gen1 = await compact_task
            # quiesced reference: same server, after the promotion
            ref = []
            for q in queries:
                status, payload = await ctl.query(q, 0.5)
                assert status == 200
                ref.append(payload["result"])
            snap = srv.metrics.snapshot()
            await ws.close()
            await ctl.close()
            return gen0, gen1, answers, ref, snap

    gen0, gen1, answers, ref, snap = asyncio.run(main())
    assert gen1 == gen0 + 1
    assert snap["counters"]["errors_total"] == 0
    assert snap["counters"]["compactions_total"] == 1
    assert len(answers) == 12 * len(ref)
    for i, msg in enumerate(answers):
        assert msg["ok"], msg
        assert msg["result"] == ref[i % len(ref)], \
            f"response {i} diverged across promotion"


def test_compaction_concurrent_request_conflict(tmp_path):
    aligner, _ = _mk_aligner(live=True, tmp_path=tmp_path)

    async def main():
        async with AlignServer(aligner) as srv:
            client = await AsyncAlignClient.connect("127.0.0.1", srv.port)
            await client.add(list(range(100)))
            first = asyncio.ensure_future(client.request(
                "POST", "/compact", {}))
            # second connection so the requests truly overlap
            other = await AsyncAlignClient.connect("127.0.0.1", srv.port)
            second = await other.request("POST", "/compact", {})
            status1, payload1 = await first
            await client.close()
            await other.close()
            return (status1, payload1), second

    (s1, p1), (s2, p2) = asyncio.run(main())
    statuses = sorted([s1, s2])
    assert statuses == [200, 409], (s1, p1, s2, p2)


def test_queue_full_on_closed_batcher():
    aligner, docs = _mk_aligner(n_docs=4)

    async def main():
        batcher = DynamicBatcher(aligner)
        await batcher.close()
        with pytest.raises(QueueFull):
            batcher.submit_query([1, 2, 3], 0.5)

    asyncio.run(main())


# --------------------------------------------------------------------------
# degraded mode, retries, and the compaction supervisor (fault harness)
# --------------------------------------------------------------------------

from repro import fault  # noqa: E402
from repro.fault import FaultPlan, Trigger  # noqa: E402
from repro.serve import CompactionSupervisor  # noqa: E402


def _mk_sharded_aligner(n_docs: int = 12, doc_len: int = 120):
    rng = np.random.default_rng(5)
    docs = [rng.integers(0, 1 << 40, size=doc_len) for _ in range(n_docs)]
    return Aligner.build(docs, similarity="multiset", seed=3, k=8,
                         shards=2), docs


def _wait_for(predicate, timeout_s: float = 15.0, interval_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


def test_shard_failure_degrades_instead_of_500():
    aligner, docs = _mk_sharded_aligner()
    with _ThreadServer(aligner) as srv:
        with AlignClient(port=srv.port) as client:
            snippet = [int(t) for t in docs[6][10:90]]   # doc 6 -> shard 0
            plan = FaultPlan(triggers=[Trigger(site="sharded.probe.s1",
                                               sticky=True)])
            try:
                fault.arm(plan)
                result = client.query(snippet, 0.5)      # 200, not 500
                assert result["degraded"] is True
                assert result["failed_shards"] == [1]
                # the healthy shard's docs still come back
                assert any(m["doc_id"] == 6 for m in result["matches"])
                health = client.healthz()
                assert health["status"] == "degraded"
                assert health["failed_shards"] == [1]
                snap = client.metrics()
                assert snap["counters"]["degraded_total"] >= 1
                assert snap["counters"]["errors_total"] == 0
                assert snap["fault"]["armed"] is True
                assert "store" in snap
            finally:
                fault.disarm()
            # fault cleared: the next query restores full health
            result = client.query(snippet, 0.5)
            assert result["degraded"] is False
            assert client.healthz()["status"] == "healthy"


def test_batcher_probe_fault_hook_maps_to_500_then_recovers():
    aligner, docs = _mk_sharded_aligner(n_docs=6)
    with _ThreadServer(aligner) as srv:
        with AlignClient(port=srv.port) as client:
            q = [int(t) for t in docs[0][10:90]]
            plan = FaultPlan(triggers=[Trigger(site="serve.batcher.probe",
                                               sticky=True)])
            try:
                fault.arm(plan)
                with pytest.raises(ServerError) as ei:
                    client.query(q, 0.5)
                assert ei.value.status == 500
            finally:
                fault.disarm()
            assert client.metrics()["counters"]["errors_total"] >= 1
            assert client.query(q, 0.5)["matches"]        # healthy again


def test_503_carries_retry_after_and_client_retries_queries():
    aligner, docs = _mk_aligner(n_docs=6)
    with _ThreadServer(aligner, retry_after_s=0.25) as srv:
        q = [int(t) for t in docs[0][10:90]]
        orig = srv.batcher.submit_query
        calls = {"n": 0, "fail_first": 0}

        def flaky(*a, **kw):
            calls["n"] += 1
            if calls["n"] <= calls["fail_first"]:
                raise QueueFull("induced shed")
            return orig(*a, **kw)

        srv.batcher.submit_query = flaky

        # a bare client surfaces the 503, and the Retry-After hint rides it
        with AlignClient(port=srv.port) as client:
            calls.update(n=0, fail_first=1)
            status, payload, headers = client._request_full(
                "POST", "/query", {"text": q, "theta": 0.5})
            assert status == 503
            assert float(headers["retry-after"]) == 0.25
            # non-idempotent endpoints never carry the retry hint
            status, _, headers = client._request_full("POST", "/nope", {})
            assert "retry-after" not in headers

        # retries=2 absorbs the shed and answers the query
        with AlignClient(port=srv.port, retries=2,
                         backoff_s=0.01, backoff_max_s=0.05) as client:
            calls.update(n=0, fail_first=1)
            result = client.query(q, 0.5)
            assert calls["n"] == 2
            assert any(m["doc_id"] == 0 for m in result["matches"])
            # more 503s than retries: the failure still surfaces
            calls.update(n=0, fail_first=10)
            with pytest.raises(ServerError) as ei:
                client.query(q, 0.5)
            assert ei.value.status == 503


def test_client_retries_reconnect_after_dropped_connection():
    import socket

    aligner, docs = _mk_aligner(n_docs=6)
    with _ThreadServer(aligner) as srv:
        q = [int(t) for t in docs[0][10:90]]
        with AlignClient(port=srv.port, retries=2, backoff_s=0.01) as client:
            assert client.query(q, 0.5)["matches"]
            # kill the keep-alive socket under the client: the retry
            # must reconnect instead of surfacing the connection error
            client._conn.sock.shutdown(socket.SHUT_RDWR)
            assert client.query(q, 0.5)["matches"]
        # without retries the same drop surfaces as a connection error
        with AlignClient(port=srv.port) as client:
            assert client.query(q, 0.5)["matches"]
            client._conn.sock.shutdown(socket.SHUT_RDWR)
            with pytest.raises(ConnectionError):
                client.query(q, 0.5)


def test_supervisor_auto_compacts_and_prunes(tmp_path):
    aligner, docs = _mk_aligner(live=True, tmp_path=tmp_path)
    sup = CompactionSupervisor(max_delta_fraction=0.01, interval_s=0.05,
                               prune_keep=1)
    rng = np.random.default_rng(11)
    with _ThreadServer(aligner, supervisor=sup) as srv:
        with AlignClient(port=srv.port) as client:
            assert client.healthz()["generation"] == 0
            new_doc = [int(t) for t in rng.integers(0, 1 << 40, 120)]
            client.add(new_doc)
            assert _wait_for(
                lambda: client.healthz()["generation"] >= 1), \
                "supervisor never compacted"
            # the counter moves after the off-band prune that follows the
            # promotion, so the new generation can be visible before it
            _wait_for(lambda: client.metrics()["counters"][
                "supervisor_compactions_total"] >= 1)
            snap = client.metrics()
            assert snap["counters"]["supervisor_compactions_total"] >= 1
            assert snap["counters"]["supervisor_failures_total"] == 0
            # the folded doc still serves from the new generation
            result = client.query(new_doc[20:100], 0.5)
            assert any(m["doc_id"] == len(docs)
                       for m in result["matches"])
    # generations beyond prune_keep were reclaimed on the way
    assert (tmp_path / "idx" / "v000001").exists()


def test_supervisor_rolls_back_after_exhausted_retries(tmp_path):
    aligner, docs = _mk_aligner(live=True, tmp_path=tmp_path)
    sup = CompactionSupervisor(max_delta_fraction=0.01, interval_s=0.05,
                               max_retries=1, backoff_base_s=0.02,
                               backoff_max_s=0.1)
    rng = np.random.default_rng(12)
    new_doc = [int(t) for t in rng.integers(0, 1 << 40, 120)]
    plan = FaultPlan(triggers=[Trigger(site="store.writer.*",
                                       sticky=True)])
    with _ThreadServer(aligner, supervisor=sup) as srv:
        with AlignClient(port=srv.port) as client:
            try:
                fault.arm(plan)
                client.add(new_doc)
                # attempts burn down: past max_retries the seal is rolled
                # back and /healthz reports degraded
                assert _wait_for(
                    lambda: client.healthz()["status"] == "degraded"), \
                    "supervisor never reported failure"
                snap = client.metrics()
                assert snap["counters"]["supervisor_retries_total"] >= 2
                assert snap["counters"]["supervisor_failures_total"] >= 1
                assert client.healthz()["generation"] == 0
                # the delta (or sealed level) kept serving the new doc
                result = client.query(new_doc[20:100], 0.5)
                assert any(m["doc_id"] == len(docs)
                           for m in result["matches"])
            finally:
                fault.disarm()
            # faults cleared: the supervisor converges and health returns
            assert _wait_for(
                lambda: client.healthz()["generation"] >= 1), \
                "supervisor never recovered"
            assert _wait_for(
                lambda: client.healthz()["status"] == "healthy")
            result = client.query(new_doc[20:100], 0.5)
            assert any(m["doc_id"] == len(docs)
                       for m in result["matches"])
