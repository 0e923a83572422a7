"""The trace reduction: on hand-made planes, and on a small trace
recorded on a TPU v5e (``chipbench/testdata/probe.xplane.pb``, a window
of the memscan cell cut to a few probe calls)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench.trace_reduce import (reduce_planes, reduce_trace,  # noqa: E402
                                    union)

SPAN = "chipbench.find_batch"
RECORDED = ROOT / "chipbench" / "testdata" / "probe.xplane.pb"


def _planes(ops, modules, spans):
    return [("/host:CPU", [("main", [(SPAN, s, d) for s, d in spans])]),
            ("/device:TPU:0", [("XLA Modules", modules),
                               ("XLA Ops", ops)])]


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_programs_and_gaps():
    # window 0..100 ns; ops busy on 10..30 (overlapping) and 60..70
    ops = [("%a = x", 10, 15), ("%b = y", 20, 10), ("%a = x", 60, 10)]
    modules = [("jit_probe(123)", 10, 20), ("jit_sweep(9)", 60, 10)]
    spans = [(0, 4), (30, 30), (95, 5)]
    got = reduce_planes(_planes(ops, modules, spans), SPAN)
    assert got["busy_s"] == pytest.approx(30e-9)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["idle_share"] == pytest.approx(0.7)
    assert got["program_s"] == {"jit_probe": pytest.approx(20e-9),
                                "jit_sweep": pytest.approx(10e-9)}
    ops_s = dict(got["device_ops"])
    assert ops_s["jit_probe/%a"] == pytest.approx(15e-9)
    assert ops_s["jit_sweep/%a"] == pytest.approx(10e-9)
    gaps = got["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 30e-9, 10e-9])
    # 30..60 lies inside a span; 0..10 and 70..100 mostly outside
    assert sorted(g[0] for g in gaps) == [
        f"host in {SPAN}", f"host outside {SPAN}", f"host outside {SPAN}"]
    assert got["busy_s"] + sum(g[1] for g in gaps) == \
        pytest.approx(got["window_s"])


def test_no_device_operation_reads_nothing():
    assert reduce_planes(_planes([], [], [(0, 5)]), SPAN) is None


def test_recorded_trace():
    got = reduce_trace(str(RECORDED), SPAN)
    assert got is not None
    assert 0 < got["busy_s"] < got["window_s"]
    assert 0.9 < got["idle_share"] < 1.0
    assert got["program_s"]["jit_probe"] > 0
    assert got["busy_s"] + sum(g[1] for g in got["idle_gaps"]) <= \
        got["window_s"] * (1 + 1e-9)
    assert any(g[0] == f"host in {SPAN}" for g in got["idle_gaps"])
    assert got["device_ops"][0][0].startswith("jit_probe/%")
