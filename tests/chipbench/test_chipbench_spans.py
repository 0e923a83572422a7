"""The device's idle time put down to the program's spans
(``chipbench/trace_spans.py``) on hand-made planes, its command on the
CPU at a tiny size, and the readers of the per-span and per-counter
metrics on hand-made records."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run, trace_spans  # noqa: E402
from chipbench.trace_spans import reduce_span_planes  # noqa: E402

NAMES = ("sketch", "probe", "probe.device", "probe.gather", "sweep",
         "sweep.group", "sweep.device", "sweep.large", "sweep.emit",
         "results", "serve.parse", "serve.respond")


def _planes(engine, loop, ops, modules):
    return [("/host:CPU", [("engine", engine), ("loop", loop)]),
            ("/device:TPU:0", [("XLA Modules", modules),
                               ("XLA Ops", ops)])]


# engine thread: probe 10..40 holding probe.device 20..30, sweep 40..90
# holding sweep.large 50..80, all inside the benchmark's own wrapper span
# (not a program span); event loop: serve.respond 0..5 and 60..65,
# serve.parse 85..95.  Device busy 20..25 (jit_probe) and 95..100.
ENGINE = [("chipbench.find_batch", 5, 90), ("probe", 10, 30),
          ("probe.device", 20, 10), ("sweep", 40, 50),
          ("sweep.large", 50, 30)]
LOOP = [("serve.respond", 0, 5), ("serve.respond", 60, 5),
        ("serve.parse", 85, 10)]
OPS = [("%a = x", 20, 5), ("%b = y", 95, 5)]
MODULES = [("jit_probe(7)", 20, 5), ("jit_sweep_grid(8)", 95, 5)]


def test_idle_time_goes_to_the_innermost_open_span():
    got = reduce_span_planes(_planes(ENGINE, LOOP, OPS, MODULES), NAMES)
    ns = 1e-9
    assert got["idle_s"] == pytest.approx(90 * ns)
    # the deepest span wins across threads (sweep.large over the loop's
    # serve.respond at 60..65); among equals the latest opened
    # (serve.parse over sweep at 85..90)
    assert got["idle_by_span"] == {
        "sweep.large": pytest.approx(30 * ns),
        "probe": pytest.approx(20 * ns),
        "sweep": pytest.approx(15 * ns),
        "serve.parse": pytest.approx(10 * ns),
        "serve.respond": pytest.approx(5 * ns),
        "probe.device": pytest.approx(5 * ns)}
    # 5..10 lies in no program span: the wrapper span does not count
    assert got["idle_unattributed_s"] == pytest.approx(5 * ns)
    assert got["idle_unattributed_share"] == pytest.approx(5 / 90)
    assert got["span_s"]["serve.respond"] == pytest.approx(10 * ns)
    assert got["child_cover"] == {"probe": pytest.approx(1 / 3),
                                  "sweep": pytest.approx(0.6)}
    assert got["program_in_span"] == {
        "jit_probe": {"probe": pytest.approx(1.0),
                      "probe.device": pytest.approx(1.0)},
        "jit_sweep_grid": {}}


def test_no_span_open_leaves_all_idle_time_unattributed():
    got = reduce_span_planes(_planes([], [], OPS, MODULES), NAMES)
    assert got["idle_by_span"] == {}
    assert got["idle_unattributed_share"] == pytest.approx(1.0)
    assert got["idle_unattributed_s"] == pytest.approx(got["idle_s"])
    assert got["child_cover"] == {}


def test_no_device_operation_reads_nothing():
    assert reduce_span_planes(_planes(ENGINE, LOOP, [], []), NAMES) is None


def test_the_program_names_its_spans():
    from repro.core.spans import NAMES as program_names
    assert trace_spans.span_names() == program_names
    assert set(NAMES) <= set(program_names)


def _tiny_root(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_tests", Path(__file__).parent / "test_chipbench_run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._tiny_root(tmp_path, "multiset", "closed")


def test_command_reads_the_traced_window(tmp_path, monkeypatch, capsys):
    # off a TPU the trace has no device plane, so the reduction reads
    # nothing; the traced window's own rate is still read
    root = _tiny_root(tmp_path)

    def main(argv):
        assert argv[-2:] == ["--trace", "1"]
        res = run.run_cell(run.load_cell(root, "tiny.cell"), 2 ** 31 + 7,
                           2.0, True)
        print(json.dumps(res))
        return 0

    monkeypatch.setattr(run, "main", main)
    reduce_trace = sys.modules["chipbench.trace_reduce"].reduce_trace
    serve_window = run.serve_window
    assert trace_spans.main(["--workload", "tiny.cell"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-2])["correct"] is True
    got = json.loads(lines[-1])
    assert got["queries_per_s"] > 0
    assert got["trace_spans"] is None
    # the run's functions are put back
    assert sys.modules["chipbench.trace_reduce"].reduce_trace is reduce_trace
    assert run.serve_window is serve_window


# --------------------------------------------------------------------------
# readers
# --------------------------------------------------------------------------

STAGES = {"probe.device": 0.5, "probe.gather": 1.0, "sweep.group": 2.0,
          "sweep.device": 0.25, "sweep.large": 8.0,
          "sweep.large.read": 1.5, "sweep.emit": 0.75, "results": 0.25,
          "serve.parse": 0.125, "serve.respond": 0.375}
TRANSFER = {"probe_windows": 5000, "groups_kept": 400,
            "host_large_groups": 100, "host_large_windows": 6000}


def _snap(scale, queries):
    return {"queries": queries,
            "stage_seconds": {k: v * scale for k, v in STAGES.items()},
            "transfer": {k: v * scale for k, v in TRANSFER.items()}}


RECORD = {"before": _snap(1, 10), "after": _snap(3, 20)}
WANT = {  # the window gained twice the table, over 10 queries
    "probe_device_ms_per_query": 100.0,
    "probe_gather_ms_per_query": 200.0,
    "group_ms_per_query": 400.0,
    "device_sweep_ms_per_query": 50.0,
    "host_sweep_ms_per_query": 1600.0,
    "host_sweep_read_ms_per_query": 300.0,
    "emit_ms_per_query": 200.0,
    "http_ms_per_query": 100.0,
    "probe_windows_per_query": 1000.0,
    "large_group_share": 0.25,
    "windows_per_large_group": 60.0,
}


def _reader(name):
    return run.load_reader(ROOT, name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_record(name):
    assert _reader(name)(RECORD) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_nothing_from_a_program_without_the_key(name):
    # the program before these spans and counters: three stages, three
    # work counters
    old = {side: {"queries": q,
                  "stage_seconds": {"sketch": 1.0, "probe": 2.0,
                                    "sweep": 3.0, "queue_wait": 4.0},
                  "transfer": {"batches": 1, "sweep_launches": 2,
                               "host_large_groups": 3}}
           for side, q in (("before", 10), ("after", 20))}
    assert _reader(name)(old) is None
    idle = {"before": _snap(1, 10), "after": _snap(1, 10)}
    assert _reader(name)(idle) is None


def test_every_new_metric_is_declared_for_the_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        m = declared[name]
        assert m["moves"] == "queries_per_s"
        assert m["workloads"] == ["memscan-multiset.scan"]
        assert (ROOT / "chipbench" / "metrics" / f"{name}.py").exists()
