"""Engine milliseconds per query building results: run extraction and
the ``Alignment``s (span ``sweep.emit``), then the ``QueryResult``s
(span ``results``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "sweep.emit", "results")
