"""Windows the probe gathered per query: the grouping's input
(``transfer_stats()["probe_windows"]``)."""

from chipbench.window import per_query


def read(rec: dict) -> float | None:
    return per_query(rec, "probe_windows")
