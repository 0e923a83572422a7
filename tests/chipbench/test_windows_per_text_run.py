"""The reader of ``windows_per_text_run`` on hand-made records, and its
entry in ``BENCHMARK.json``."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run  # noqa: E402

NAME = "windows_per_text_run"


def _record(before: dict, after: dict) -> dict:
    return {"before": {"queries": 10, "stage_seconds": {},
                       "transfer": before},
            "after": {"queries": 20, "stage_seconds": {},
                      "transfer": after}}


@pytest.mark.parametrize("before, after, want", [
    # the window gained 55,000 windows in 1,000 runs
    ({"probe_windows": 5000, "probe_text_runs": 100},
     {"probe_windows": 60000, "probe_text_runs": 1100}, 55.0),
    # one window per run: every run a single window
    ({"probe_windows": 0, "probe_text_runs": 0},
     {"probe_windows": 40, "probe_text_runs": 40}, 1.0),
])
def test_reader_on_a_hand_made_record(before, after, want):
    read = run.load_reader(ROOT, NAME)
    assert read(_record(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before, after", [
    # a program without the counter (the parent of the test)
    ({"probe_windows": 50}, {"probe_windows": 200}),
    # no probe hit in the window
    ({"probe_windows": 5, "probe_text_runs": 5},
     {"probe_windows": 5, "probe_text_runs": 5}),
])
def test_reader_reads_nothing_without_the_counter_or_the_runs(before,
                                                              after):
    assert run.load_reader(ROOT, NAME)(_record(before, after)) is None


def test_the_metric_is_declared_for_the_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "windows/run", "better": "higher",
                 "source": "program_counter", "layer": "grouping and sweep",
                 "moves": "queries_per_s",
                 "workloads": ["memscan-multiset.scan"]}
