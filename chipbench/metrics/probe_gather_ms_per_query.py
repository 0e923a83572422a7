"""Engine milliseconds per query expanding the probe extents and reading
the windows' text ids off the mmap (span ``probe.gather``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "probe.gather")
