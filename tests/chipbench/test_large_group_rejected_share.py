"""The reader of ``large_group_rejected_share`` on hand-made records, and
its entry in ``BENCHMARK.json``."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import run  # noqa: E402

NAME = "large_group_rejected_share"


def _record(before: dict, after: dict) -> dict:
    return {"before": {"queries": 10, "stage_seconds": {},
                       "transfer": before},
            "after": {"queries": 20, "stage_seconds": {},
                      "transfer": after}}


@pytest.mark.parametrize("before, after, want", [
    # the window gained 150 large groups, 147 of them rejected
    ({"host_large_groups": 50, "host_large_rejected": 49},
     {"host_large_groups": 200, "host_large_rejected": 196}, 0.98),
    # nothing rejected, and everything rejected
    ({"host_large_groups": 0, "host_large_rejected": 0},
     {"host_large_groups": 40, "host_large_rejected": 0}, 0.0),
    ({"host_large_groups": 0, "host_large_rejected": 0},
     {"host_large_groups": 40, "host_large_rejected": 40}, 1.0),
])
def test_reader_on_a_hand_made_record(before, after, want):
    read = run.load_reader(ROOT, NAME)
    assert read(_record(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before, after", [
    # a program without the counter (the parent of the test)
    ({"host_large_groups": 50}, {"host_large_groups": 200}),
    # no large group in the window
    ({"host_large_groups": 5, "host_large_rejected": 5},
     {"host_large_groups": 5, "host_large_rejected": 5}),
])
def test_reader_reads_nothing_without_the_counter_or_the_groups(before,
                                                                after):
    assert run.load_reader(ROOT, NAME)(_record(before, after)) is None


def test_the_metric_is_declared_for_the_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "fraction", "better": "higher",
                 "source": "program_counter", "layer": "grouping and sweep",
                 "moves": "queries_per_s",
                 "workloads": ["memscan-multiset.scan"]}
