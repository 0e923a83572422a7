"""The harness end to end on the CPU at a tiny size: files found by name,
``correct`` true on a sound run and false under each planted fault, the
control failing, and no result off a TPU."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run  # noqa: E402
from chipbench.control import step_control  # noqa: E402

SEED = 2 ** 31 + 12345
SECONDS = 2.0

NEW_METRIC = '''"""Requests answered in the window, per second of set-up (a test
metric)."""


def read(rec):
    return len(rec["requests"]) / rec["setup_s"]
'''


def _tiny_root(tmp: Path, similarity: str, loop: str) -> Path:
    """A copy of the benchmark with one more configuration, traffic mix,
    cell and per-layer metric, each added as files and entries only."""
    root = tmp / "bench"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    src = {"multiset": "memscan-multiset", "tfidf": "gencheck-tfidf"}
    cfg = json.loads((ROOT / "chipbench" / "configs" /
                      f"{src[similarity]}.json").read_text())
    # as in the cell: twice as many clients as a batch holds, so that
    # every batch is full
    cfg.update(name="tiny", corpus_tokens=20000,
               server=dict(cfg["server"], max_batch=2))
    cfg["corpus"].update(vocab=2000, doc_len=[64, 256])
    (root / "chipbench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "tiny-mix.json").write_text(
        json.dumps({"loop": loop, "clients": 4, "rate_qps": 6.0,
                    "connections": 4, "shape_seed": 1,
                    "length": {"dist": "loguniform", "min": 16, "max": 48},
                    "planted_share": 0.4, "edit_rate": [0.0, 0.1],
                    "edit_ops": ["substitute", "delete", "insert"],
                    "strata": 4}))
    (root / "chipbench" / "metrics" / "answered_per_setup_s.py").write_text(
        NEW_METRIC)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": ["corpus_tokens"], "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answered_per_setup_s.tiny",
                               "unit": "1/s", "better": "higher",
                               "source": "host_clock", "layer": "test",
                               "moves": "queries_per_s",
                               "workloads": ["tiny.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def multiset_root(tmp_path_factory):
    return _tiny_root(tmp_path_factory.mktemp("ms"), "multiset", "closed")


def test_files_are_found_by_name(multiset_root):
    cell = run.load_cell(multiset_root, "tiny.cell")
    assert cell.config["name"] == "tiny"
    assert cell.traffic["clients"] == 4
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "queries_per_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["answered_per_setup_s.tiny"]
    read = run.load_reader(multiset_root, "answered_per_setup_s.tiny")
    assert read({"requests": [1, 2, 3], "setup_s": 1.5}) == 2.0


@pytest.mark.parametrize("similarity,loop,trace", [
    ("multiset", "closed", True), ("tfidf", "open", False)])
def test_sound_run_is_correct(tmp_path, multiset_root, similarity, loop,
                              trace):
    root = (multiset_root if similarity == "multiset"
            else _tiny_root(tmp_path, similarity, loop))
    res = run.run_cell(run.load_cell(root, "tiny.cell"), SEED, SECONDS,
                       trace)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["answers_compared"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert set(res["metrics"]) == {"answered_per_setup_s.tiny"}
    else:
        assert set(res["metrics"]) == {"setup_s", "queries_per_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())


class AlteredAnswers:
    """A fault: the first match of every answer has its last cell cut."""

    def __init__(self, aligner):
        self._aligner = aligner

    def __getattr__(self, name):
        return getattr(self._aligner, name)

    def find_batch(self, texts, theta, **kw):
        out = []
        for r in self._aligner.find_batch(texts, theta, **kw):
            if r.matches:
                m = r.matches[0]
                il, ih, jl, jh = m.blocks[-1]
                blocks = m.blocks[:-1] + ([(il, ih, jl, jh - 1)]
                                          if jh > jl else [])
                r = dataclasses.replace(r, matches=[
                    dataclasses.replace(m, blocks=blocks)] + r.matches[1:])
            out.append(r)
        return out


class HalfBatch(AlteredAnswers):
    """A fault: the second half of every batch is answered with nothing."""

    def find_batch(self, texts, theta, **kw):
        res = self._aligner.find_batch(texts, theta, **kw)
        keep = (len(res) + 1) // 2
        return res[:keep] + [dataclasses.replace(r, matches=[])
                             for r in res[keep:]]


@pytest.mark.parametrize("fault", ["altered", "half_batch", "store"])
def test_a_fault_makes_the_run_incorrect(multiset_root, monkeypatch,
                                         fault):
    cell = run.load_cell(multiset_root, "tiny.cell")
    wrap = {"altered": AlteredAnswers, "half_batch": HalfBatch}.get(fault)
    if fault == "store":
        # the store is built from another corpus than the configuration's
        built = run.ensure_store

        def misbuilt(cell_):
            cfg = dict(cell_.config, corpus=dict(
                cell_.config["corpus"],
                corpus_seed=cell_.config["corpus"]["corpus_seed"] + 1))
            path = multiset_root / "misbuilt.json"
            path.write_text(json.dumps(cfg))
            return built(dataclasses.replace(cell_, config=cfg,
                                             config_path=path))

        monkeypatch.setattr(run, "ensure_store", misbuilt)
    res = run.run_cell(cell, SEED + 1, SECONDS, False, wrap=wrap)
    assert res["correct"] is False
    assert res["checks"]["answers_wrong"]["value"] > 0


def test_step_control_is_incorrect(multiset_root):
    cell = run.load_cell(multiset_root, "tiny.cell")
    got = step_control(cell, SEED, SECONDS)
    assert got["control_need"] == got["need"] - 1
    assert got["answers_wrong"] > 0


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "memscan-multiset.scan", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no run" in out.stderr
