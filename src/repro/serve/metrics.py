"""Serve-path observability: counters, log-bucket histograms, per-stage
timing — everything ``/metrics`` reports and ``bench_serve`` asserts on.

All updates take one small lock (they happen on the event loop and the
engine thread); ``snapshot()`` returns a plain JSON-able dict.
"""

from __future__ import annotations

import threading

from ..core.spans import NAMES as SPAN_NAMES

_NBUCKETS = 64
_FIRST_EDGE_S = 1e-5        # 10 µs; edges double per bucket → ~58 s cap


class Histogram:
    """Fixed log2-bucket histogram of positive values (seconds, counts).

    Bucket ``i`` holds values in ``(edge * 2**(i-1), edge * 2**i]`` with
    bucket 0 catching everything ``<= edge``; quantiles are read as the
    upper edge of the bucket where the cumulative count crosses — a <=2x
    overestimate by construction, which is exactly the conservative side
    a latency SLO wants.
    """

    def __init__(self, first_edge: float = _FIRST_EDGE_S):
        self.first_edge = first_edge
        self.counts = [0] * _NBUCKETS
        self.total = 0
        self.sum = 0.0
        self.max = 0.0

    def add(self, v: float) -> None:
        b = 0
        edge = self.first_edge
        while v > edge and b < _NBUCKETS - 1:
            edge *= 2.0
            b += 1
        self.counts[b] += 1
        self.total += 1
        self.sum += v
        if v > self.max:
            self.max = v

    def quantile(self, q: float) -> float:
        if not self.total:
            return 0.0
        target = q * self.total
        seen = 0
        edge = self.first_edge
        for c in self.counts:
            seen += c
            if seen >= target:
                return min(edge, self.max)
            edge *= 2.0
        return self.max

    def summary(self) -> dict:
        return {"count": self.total,
                "mean": self.sum / self.total if self.total else 0.0,
                "p50": self.quantile(0.50),
                "p99": self.quantile(0.99),
                "max": self.max}


class ServeMetrics:
    """All serve-path counters and histograms, behind one lock."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters = {
            "requests_total": 0,       # queries admitted
            "responses_total": 0,      # queries answered with results
            "rejected_total": 0,       # 503: queue at capacity
            "expired_total": 0,        # 504: deadline passed before probe
            "errors_total": 0,         # engine-side exceptions
            "adds_total": 0,
            "adds_deduped_total": 0,   # retried request_ids answered from
            #                            the dedup window, nothing indexed
            "wal_group_commits_total": 0,   # durable-ack flush barriers
            "compactions_total": 0,
            "batches_total": 0,        # find_batch calls issued
            "degraded_total": 0,       # partial (shard-skipping) responses
            "supervisor_compactions_total": 0,
            "supervisor_retries_total": 0,   # failed background attempts
            "supervisor_failures_total": 0,  # gave up past max_retries
            "pruned_generations_total": 0,   # store dirs reclaimed
        }
        self.latency = Histogram()         # enqueue -> response, seconds
        self.queue_wait = Histogram()      # enqueue -> batch dispatch
        self.batch_size = Histogram(first_edge=1.0)
        # adds acknowledged per durable flush — how well group commit is
        # amortizing fsyncs (mean ~1 means per-record fsync cost)
        self.wal_group_commit = Histogram(first_edge=1.0)
        # every engine and front-end span (repro.core.spans), present
        # from the start so a delta over any window exists
        self.stage_seconds = dict.fromkeys(SPAN_NAMES + ("queue_wait",),
                                           0.0)

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            self.counters[name] += by

    def observe_batch(self, size: int, queue_waits, stage: dict) -> None:
        """One dispatched batch: its occupancy, each member's queue wait,
        and the engine's seconds per span for the ``find_batch`` call
        (every key its ``stage`` dict carries)."""
        with self._lock:
            self.counters["batches_total"] += 1
            self.batch_size.add(float(size))
            for w in queue_waits:
                self.queue_wait.add(w)
                self.stage_seconds["queue_wait"] += w
            for key, seconds in stage.items():
                self.stage_seconds[key] = \
                    self.stage_seconds.get(key, 0.0) + seconds

    def observe_span(self, name: str, seconds: float) -> None:
        """Seconds of one span outside the engine's batches (the front
        end's ``serve.parse``/``serve.respond``, on the event loop)."""
        with self._lock:
            self.stage_seconds[name] = \
                self.stage_seconds.get(name, 0.0) + seconds

    def observe_latency(self, seconds: float) -> None:
        with self._lock:
            self.counters["responses_total"] += 1
            self.latency.add(seconds)

    def observe_group_commit(self, size: int) -> None:
        """One write group made durable: ``size`` adds shared the flush."""
        with self._lock:
            self.counters["wal_group_commits_total"] += 1
            self.wal_group_commit.add(float(size))

    def snapshot(self) -> dict:
        with self._lock:
            return {"counters": dict(self.counters),
                    "latency_s": self.latency.summary(),
                    "queue_wait_s": self.queue_wait.summary(),
                    "batch_size": self.batch_size.summary(),
                    "wal_group_commit": self.wal_group_commit.summary(),
                    "stage_seconds": dict(self.stage_seconds)}
