"""Probe windows per text run the fused grouping reads
(``probe_windows`` over ``probe_text_runs``, ``transfer_stats()``)."""

from chipbench.window import ratio


def read(rec: dict) -> float | None:
    return ratio(rec, "probe_windows", "probe_text_runs")
