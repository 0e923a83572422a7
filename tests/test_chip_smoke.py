"""``chip_smoke.py`` end to end at a tiny size: CPU, interpret mode.

The smoke's phases run in this process; the test lifts its TPU
requirement by replacing ``require_tpu`` (the script itself has no flag
for that).  Inside ``main`` every device-plan response is checked block
for block against ``plan="cpu"``, every planted span must be found, and
the resident arena must upload once — so a pass here means all of that
held.
"""

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def on_cpu(smoke, monkeypatch):
    import repro.compile_cache
    monkeypatch.setattr(smoke, "require_tpu", smoke.describe_device)
    # keep this test process's compiles out of the checkout's cache
    monkeypatch.setattr(repro.compile_cache, "configure_compile_cache",
                        lambda: None)
    return smoke


def test_smoke_phases_pass_at_tiny_size(on_cpu, tmp_path, capsys):
    assert on_cpu.main(["--seed", "3", "--tokens", "6000",
                        "--workdir", str(tmp_path / "work")]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["count"] >= 1
    assert isinstance(last["device"]["kind"], str)
    text = "\n".join(lines[:-1])
    assert "corpus cut: " in text                 # --tokens below full size
    n = on_cpu.QUERIES
    # corpus queries + the live phase's planted queries of added docs
    assert (f"device-plan responses identical to plan=cpu: "
            f"{n + on_cpu.ADD_QUERIES} (planted matched: {n // 2} corpus, "
            f"{on_cpu.ADD_QUERIES} added)") in text
    assert f"fused frozen-path queries identical to plan=cpu: {n}" in text
    assert "sweep kernel groups identical to the host sweep: 192" in text
    assert not (tmp_path / "work").exists()       # scratch store removed


def test_smoke_refuses_without_tpu(smoke, tmp_path, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main(["--tokens", "1000", "--workdir", str(tmp_path / "w")])
    assert exc.value.code != 0
    out = capsys.readouterr().out
    assert "no TPU" in out and '"ok"' not in out


def test_planted_queries_reach_theta_and_are_edited(smoke):
    from repro.api import Aligner
    from repro.core.query import estimate_similarity
    rng = np.random.default_rng(0)
    p = smoke.zipf_probs()
    docs = smoke.make_corpus(rng, 4000, p)
    aligner = Aligner.build(docs, similarity="tfidf", k=smoke.K,
                            pipeline="columnar")
    queries, _ = smoke.make_queries(rng, dict(enumerate(docs)),
                                    aligner._index, 6, p)
    planted = [(q, src) for q, src in queries if src is not None]
    assert len(planted) == 3
    for q, (doc_id, s, e) in planted:
        span = docs[doc_id][s:e + 1]
        assert smoke.QUERY_LEN[0] <= len(span) <= smoke.QUERY_LEN[1]
        assert not np.array_equal(q, span)        # edited
        assert estimate_similarity(aligner._index, q, span) >= smoke.THETA
