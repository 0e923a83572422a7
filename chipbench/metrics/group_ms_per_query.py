"""Engine milliseconds per query grouping the gathered windows by
(query, text): the lexsort and the distinct-coordinate prefilter (span
``sweep.group``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "sweep.group")
