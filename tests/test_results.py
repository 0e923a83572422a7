"""Typed request/response API units: Match / QueryResult round-trips,
QueryOptions coercion + legacy-kwarg deprecation, the batched-sketch
fast path, and the live empty-delta probe short-circuit."""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.api import Aligner, Match, QueryOptions, QueryResult
from repro.core.results import coerce_query_options


def _mk(n_docs: int = 20, doc_len: int = 100, **kw):
    rng = np.random.default_rng(2)
    docs = [rng.integers(0, 1 << 40, size=doc_len) for _ in range(n_docs)]
    return Aligner.build(docs, similarity="multiset", seed=3, k=8, **kw), docs


# -- Match / QueryResult ----------------------------------------------------


def test_match_json_roundtrip():
    m = Match(doc_id=3, span=(2, 40), query_span=(0, 38),
              estimated_similarity=0.75,
              blocks=[(2, 10, 0, 8), (12, 40, 10, 38)])
    m2 = Match.from_dict(json.loads(json.dumps(m.to_dict())))
    assert m2 == m
    assert m2.text_id == 3                       # legacy alias
    doc_id, span, qspan, sim = m2                # tuple protocol
    assert (doc_id, span, qspan, sim) == (3, (2, 40), (0, 38), 0.75)


def test_query_result_container_and_json():
    aligner, docs = _mk()
    res = aligner.find([int(t) for t in docs[4][10:80]], 0.5)
    assert isinstance(res, QueryResult)
    assert bool(res) and len(res) == len(res.matches)
    assert res[0].doc_id in [m.doc_id for m in res]
    rt = QueryResult.from_json(res.to_json())
    assert rt == res
    assert rt.theta == 0.5 and rt.query_len == 70


def test_estimated_similarity_bounds():
    aligner, docs = _mk()
    res = aligner.find([int(t) for t in docs[0][:80]], 0.5)
    assert res
    for m in res:
        assert 0.5 <= m.estimated_similarity <= 1.0


def test_find_batch_matches_looped_find():
    aligner, docs = _mk()
    queries = [[int(t) for t in d[:60]] for d in docs[:6]]
    batched = aligner.find_batch(queries, 0.5)
    looped = [aligner.find(q, 0.5) for q in queries]
    assert batched == looped


def test_legacy_tuples_deprecated():
    aligner, docs = _mk()
    q = [int(t) for t in docs[0][:60]]
    with pytest.warns(DeprecationWarning, match="legacy_tuples"):
        raw = aligner.find(q, 0.5, legacy_tuples=True)  # repro: allow[RPR402]
    assert not isinstance(raw, QueryResult)
    assert raw and hasattr(raw[0], "blocks")     # bare Alignment list


# -- QueryOptions -----------------------------------------------------------


def test_query_options_batch_key_excludes_sketches():
    a = QueryOptions(sketches=[[1, 2]])
    b = QueryOptions(sketches=None)
    assert a.batch_key() == b.batch_key()
    assert QueryOptions(sketch_backend="pallas").batch_key() != \
        b.batch_key()


def test_query_options_dict_roundtrip_rejects_unknown():
    opts = QueryOptions(sketch_backend="pallas", fanout="serial")
    assert QueryOptions.from_dict(opts.to_dict()) == opts
    with pytest.raises(ValueError, match="unknown"):
        QueryOptions.from_dict({"sketch_backnd": "exact"})
    with pytest.raises(ValueError):
        QueryOptions.from_dict({"sketches": [[1]]})


def test_legacy_kwargs_warn_and_coerce():
    aligner, docs = _mk()
    q = [int(t) for t in docs[0][:60]]
    with pytest.warns(DeprecationWarning, match="sketch_backend"):
        res = aligner.find_batch(  # repro: allow[RPR404] (tests the shim)
            [q], 0.5, sketch_backend="exact")
    assert res == aligner.find_batch(
        [q], 0.5, options=QueryOptions(sketch_backend="exact"))
    # `backend` renames to sketch_backend, and the warning says so
    with pytest.warns(DeprecationWarning, match="sketch_backend"):
        coerced = coerce_query_options(None, "find_batch", backend="exact")
    assert coerced == QueryOptions(sketch_backend="exact")


def test_mixing_options_and_legacy_kwargs_is_an_error():
    with pytest.raises(TypeError, match="both"):
        coerce_query_options(QueryOptions(), "find_batch",
                             sketch_backend="exact")


def test_alignment_index_reexport_removed():
    import repro.core
    assert not hasattr(repro.core, "AlignmentIndex")
    # repro: allow[RPR403] (the test pins the shim's canonical home)
    from repro.core.index import AlignmentIndex
    assert AlignmentIndex is not None             # repro: allow[RPR403]


# -- batched sketching ------------------------------------------------------


def test_weighted_sketch_batch_parity_with_loop():
    """The vectorized exact batch sketch must be bit-identical to the
    per-text path — mixed lengths, repeated tokens, huge token ids."""
    from repro.core import make_scheme
    rng = np.random.default_rng(8)
    corpus = [rng.integers(0, 5000, size=150) for _ in range(20)]
    scheme = make_scheme("tfidf", seed=11, k=16, corpus=corpus)
    texts = ([rng.integers(0, 5000, size=int(n))
              for n in rng.integers(1, 200, size=15)]
             + [rng.integers(0, 1 << 60, size=40) for _ in range(5)]
             + [np.array([7] * 30)])              # single distinct token
    assert scheme.sketch_batch(texts) == [scheme.sketch(t) for t in texts]


def test_weighted_sketch_batch_empty_text_falls_back():
    from repro.core import make_scheme
    scheme = make_scheme("weighted", seed=1, k=4)
    with pytest.raises((ValueError, IndexError)):
        scheme.sketch_batch([np.array([1, 2, 3]), np.array([], np.int64)])


# -- live empty-delta short-circuit -----------------------------------------


def test_live_empty_delta_skips_delta_probe(tmp_path, monkeypatch):
    """A freshly opened live store has zero delta tables; its batch
    queries must probe the frozen level only."""
    import importlib
    Q = importlib.import_module("repro.core.query")
    rng = np.random.default_rng(4)
    docs = [rng.integers(0, 1 << 40, size=100) for _ in range(12)]
    store = str(tmp_path / "idx")
    Aligner.build(docs, similarity="multiset", seed=3, k=8,
                  pipeline="columnar", store=store)
    aligner = Aligner.load(store, live=True)

    calls = []
    orig = Q._probe_level

    def counting(level, *args):
        calls.append(level)
        return orig(level, *args)

    monkeypatch.setattr(Q, "_probe_level", counting)
    queries = [[int(t) for t in docs[0][:60]]]
    res = aligner.find_batch(queries, 0.5)
    assert res[0], "self-query must hit"
    assert len(calls) == 1, \
        f"empty delta still probed: {len(calls)} level probes"

    # after one add the delta level probes too
    aligner.add([int(t) for t in rng.integers(0, 1 << 40, 100)])
    calls.clear()
    aligner.find_batch(queries, 0.5)
    assert len(calls) == 2
