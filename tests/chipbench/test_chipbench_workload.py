"""Traffic from the seed: the same seed sends the same queries; every seed
sends the same shape of work (lengths, planted share, work strata)."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench.workload import Traffic, make_corpus  # noqa: E402

CFG = {"name": "tiny", "corpus_tokens": 20000, "similarity": "multiset",
       "k": 16, "theta": 0.8, "hash_seed": 0,
       "corpus": {"vocab": 2000, "zipf_s": 1.1, "doc_len": [64, 256],
                  "corpus_seed": 3}}
MIX = {"loop": "closed", "clients": 2, "shape_seed": 9, "planted_share": 0.1,
       "length": {"dist": "loguniform", "min": 16, "max": 64},
       "edit_rate": [0.0, 0.1], "edit_ops": ["substitute", "insert"],
       "strata": 4}
BIG = 2 ** 31 + 99


@pytest.fixture(scope="module")
def docs():
    return make_corpus(CFG)


def _same(a, b):
    return len(a) == len(b) and all(
        np.array_equal(x[0], y[0]) and x[1] == y[1] for x, y in zip(a, b))


def test_the_same_seed_sends_the_same_queries(docs):
    a = [Traffic(CFG, MIX, BIG, 10, docs).query(n) for n in range(40)]
    b = [Traffic(CFG, MIX, BIG, 10, docs).query(n) for n in range(40)]
    c = [Traffic(CFG, MIX, BIG + 1, 10, docs).query(n) for n in range(40)]
    assert _same(a, b) and not _same(a, c)


def test_every_seed_plants_the_same_share(docs):
    for seed in (1, BIG, -7):
        t = Traffic(CFG, MIX, seed, 10, docs)
        for start in (0, 13, 101):
            planted = [t.planted(n) for n in range(start, start + 50)]
            assert sum(planted) == 5


def test_every_seed_sends_each_work_stratum_equally_often(docs):
    strata = MIX["strata"]
    for seed in (5, BIG):
        t = Traffic(CFG, MIX, seed, 10, docs)
        fresh = [n for n in range(200) if not t.planted(n)][:strata * 8]
        bins = [t._stratum(n) for n in fresh]
        assert np.bincount(bins, minlength=strata).tolist() == [8] * strata
        # and each fresh query's work lies in its stratum
        edges = np.concatenate([[-np.inf], t.edges, [np.inf]])
        for n, s in zip(fresh[:12], bins):
            q, plant = t.query(n)
            assert plant is None
            work = t.reference.candidates(q)
            assert edges[s] - 1 <= work < edges[s + 1]


def test_open_loop_sends_the_same_lengths_and_gaps_in_another_order(docs):
    mix = dict(MIX, loop="open", rate_qps=20.0, connections=2)
    a, b = Traffic(CFG, mix, 1, 10, docs), Traffic(CFG, mix, 2, 10, docs)
    assert len(a.due) == len(b.due) == 200
    assert sorted(a.lengths) == sorted(b.lengths)
    gaps = [sorted(np.diff(t.due, prepend=0.0)) for t in (a, b)]
    assert gaps[0] == pytest.approx(gaps[1], abs=1e-9)
    assert not np.array_equal(a.due, b.due)
    assert a.due[-1] < 10
