"""Engine milliseconds per query sweeping the large groups on the host,
their mmap row reads included (span ``sweep.large``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "sweep.large")
