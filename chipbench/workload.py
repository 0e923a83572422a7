"""Corpus and traffic, drawn from a configuration and a traffic mix.

Neither JAX nor the program is imported here: the load generator (a
process that must stay off the chip) and the plain reference both use it.

* The corpus is fixed per configuration: it is drawn from the
  configuration's ``corpus_seed`` and stands in for the deployment's
  dataset (Zipf-ranked tokens, documents of uniform length).
* Everything the traffic sends is drawn from ``--seed``.  The *shape* of
  the traffic (the pool of query lengths, the open loop's inter-arrival
  gaps) is drawn once from the mix's own ``shape_seed``; ``--seed`` only
  permutes it, sets the phase of the planted queries (exactly the mix's
  share of any run of queries) and draws the tokens, so every seed sends
  the same amount of work in another order.
* Query ``n`` of a run is a pure function of ``(seed, n)``: the reference
  regenerates exactly the tokens the load generator sent.
"""

from __future__ import annotations

import math

import numpy as np

POOL = 4096            # closed-loop query lengths; query n takes entry n % POOL
_PLANT_STREAM = 1      # rng stream ids under the seed
_SHAPE_STREAM = 2
_STRATA_STREAM = 3
_CALIBRATION = 32      # calibration queries per stratum
_TRIES = 64            # draws per stratum before a query is taken as drawn


def _seed_words(seed: int) -> list[int]:
    """A seed of any size (negative too) as SeedSequence words."""
    seed = int(seed) & ((1 << 128) - 1)
    return [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
            (seed >> 64) & 0xFFFFFFFF, seed >> 96]


def zipf_probs(vocab: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** s
    return p / p.sum()


def make_corpus(cfg: dict) -> list[np.ndarray]:
    """The configuration's documents (token arrays), from ``corpus_seed``."""
    c = cfg["corpus"]
    rng = np.random.default_rng(c["corpus_seed"])
    lo, hi = c["doc_len"]
    lens, total = [], 0
    while total < cfg["corpus_tokens"]:
        n = int(rng.integers(lo, hi + 1))
        lens.append(n)
        total += n
    p = zipf_probs(c["vocab"], c["zipf_s"])
    toks = rng.choice(c["vocab"], size=total, p=p).astype(np.int64)
    return np.split(toks, np.cumsum(lens)[:-1])


class Traffic:
    """The queries (and, for an open loop, the due times) of one run."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, seconds: float,
                 docs: list[np.ndarray]):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.docs = docs
        c = cfg["corpus"]
        self.vocab = c["vocab"]
        self.p = zipf_probs(c["vocab"], c["zipf_s"])
        self.cdf = np.cumsum(self.p)
        shape = np.random.default_rng(traffic["shape_seed"])
        order = np.random.default_rng(_seed_words(seed) + [_SHAPE_STREAM])
        self.due = None
        size = POOL
        if traffic["loop"] == "open":
            # Poisson arrivals conditioned on their count: the sorted
            # uniform arrival times of the shape seed, gaps permuted by seed
            size = max(1, round(traffic["rate_qps"] * seconds))
            t = np.sort(shape.uniform(0.0, seconds, size=size))
            gaps = np.diff(np.concatenate([[0.0], t]))
            self.due = np.cumsum(order.permutation(gaps))
        # every seed sends the same lengths (all of them, in an open loop)
        self.lengths = order.permutation(
            self._lengths(shape, traffic["length"], size))
        self.phase = order.random()
        self._reference = None
        self.edges = None
        if traffic.get("strata"):
            self.edges = self._calibrate(traffic["strata"])
        self._blocks: dict[int, np.ndarray] = {}

    @property
    def reference(self):
        """The plain reference over the corpus (built on first use)."""
        if self._reference is None:
            from chipbench.reference import Reference, load_scheme
            self._reference = Reference(self.docs,
                                        load_scheme(self.cfg, self.docs),
                                        self.cfg["theta"])
        return self._reference

    def _work(self, rng, tokens) -> float:
        """A fresh query's work: the documents the index has to look at
        for it (ties broken at random), which sets its cost."""
        return self.reference.candidates(tokens) + rng.random()

    def _calibrate(self, strata: int) -> np.ndarray:
        """The inner edges of ``strata`` equally likely bins of a fresh
        query's work, from queries of the mix's shape seed alone."""
        shape = np.random.default_rng([self.traffic["shape_seed"],
                                       _STRATA_STREAM])
        n = _CALIBRATION * strata
        lengths = self._lengths(shape, self.traffic["length"], n)
        work = [self._work(shape, self._zipf(shape, int(m)))
                for m in lengths]
        return np.quantile(work, np.arange(1, strata) / strata)

    def _stratum(self, n: int) -> int:
        """The work bin of fresh query n: each bin once in every
        ``strata`` consecutive fresh queries, in an order drawn from the
        seed."""
        strata = self.traffic["strata"]
        fresh = n - math.floor(n * self.traffic["planted_share"] +
                               self.phase)
        block = fresh // strata
        if block not in self._blocks:
            self._blocks[block] = np.random.default_rng(
                _seed_words(self.seed) + [_STRATA_STREAM, block]
            ).permutation(strata)
        return int(self._blocks[block][fresh % strata])

    def planted(self, n: int) -> bool:
        """Whether query n is a planted span: exactly ``planted_share`` of
        any run of consecutive queries (to one query), at a phase drawn
        from the seed."""
        share = self.traffic["planted_share"]
        return math.floor((n + 1) * share + self.phase) > \
            math.floor(n * share + self.phase)

    @staticmethod
    def _lengths(shape, spec: dict, size: int) -> np.ndarray:
        lo, hi = spec["min"], spec["max"]
        if spec["dist"] == "fixed":
            return np.full(size, lo, np.int64)
        if spec["dist"] == "loguniform":
            x = np.exp(shape.uniform(math.log(lo), math.log(hi + 1), size))
            return np.clip(np.floor(x), lo, hi).astype(np.int64)
        raise ValueError(f"unknown length distribution {spec['dist']!r}")

    def query(self, n: int) -> tuple[np.ndarray, tuple | None]:
        """Query ``n``: (tokens, plant) where plant is (doc id, span start,
        span end) or None for fresh Zipf text."""
        length = int(self.lengths[n % len(self.lengths)])
        rng = np.random.default_rng(_seed_words(self.seed) +
                                    [_PLANT_STREAM, n])
        if not self.planted(n):
            return self._fresh(n, length, rng), None
        doc_id = int(rng.integers(len(self.docs)))
        doc = self.docs[doc_id]
        length = min(length, len(doc))
        s = int(rng.integers(0, len(doc) - length + 1))
        span = doc[s:s + length]
        return self._edit(rng, span), (doc_id, s, s + length - 1)

    def _zipf(self, rng, n: int) -> np.ndarray:
        """n tokens drawn from the corpus's Zipf distribution."""
        return np.minimum(np.searchsorted(self.cdf, rng.random(n),
                                          side="right"),
                          self.vocab - 1).astype(np.int64)

    def _fresh(self, n: int, length: int, rng) -> np.ndarray:
        """Fresh Zipf text; with ``strata``, drawn until its work falls in
        the query's bin (a natural draw of the bin, so the mix keeps its
        distribution while every run sends each bin equally often)."""
        if self.edges is None:
            return self._zipf(rng, length)
        s = self._stratum(n)
        lo = self.edges[s - 1] if s > 0 else -np.inf
        hi = self.edges[s] if s < len(self.edges) else np.inf
        for _ in range(_TRIES * self.traffic["strata"]):
            q = self._zipf(rng, length)
            if lo <= self._work(rng, q) < hi:
                break
        return q

    def _edit(self, rng, span: np.ndarray) -> np.ndarray:
        """A share of the span's tokens edited, drawn from the mix's
        ``edit_rate`` range; each edit one of the mix's ``edit_ops``."""
        lo, hi = self.traffic["edit_rate"]
        rate = rng.uniform(lo, hi)
        n = min(len(span), round(rate * len(span)))
        out = list(int(t) for t in span)
        if n == 0:
            return np.asarray(out, np.int64)
        pos = np.sort(rng.choice(len(span), size=n, replace=False))[::-1]
        ops = rng.choice(self.traffic["edit_ops"], size=n)
        new = self._zipf(rng, n)
        for i, op, t in zip(pos, ops, new):
            if op == "substitute":
                out[i] = int(t)
            elif op == "delete" and len(out) > 1:
                del out[i]
            elif op == "insert":
                out.insert(i, int(t))
        return np.asarray(out, np.int64)
