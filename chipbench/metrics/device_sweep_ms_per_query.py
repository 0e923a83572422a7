"""Engine milliseconds per query in the device sweep of small groups, from
the row gather's dispatch through the read-back of the coverage grids,
every size bucket (span ``sweep.device``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "sweep.device")
