"""Engine milliseconds per query in the resident-arena probe call, from
its dispatch through the read-back of the extents (span
``probe.device``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "probe.device")
