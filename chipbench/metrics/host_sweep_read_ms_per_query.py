"""Engine milliseconds per query reading the large groups' window rows off
the mmap (``sweep.large.read``, inside ``sweep.large``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "sweep.large.read")
