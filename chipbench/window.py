"""What the server's counters gained over the window: the difference of
the run record's ``after`` and ``before`` snapshots (``stage_seconds``,
``transfer``, ``queries``), per query or per other counter.

Every function returns ``None`` when a snapshot lacks a key it reads (a
program older than the key) or when it would divide by zero.
"""

from __future__ import annotations


def delta(rec: dict, section: str, key: str) -> float | None:
    """``after - before`` of ``rec[...][section][key]``."""
    got = [(rec.get(side) or {}).get(section, {}).get(key)
           for side in ("after", "before")]
    return None if None in got else got[0] - got[1]


def queries(rec: dict) -> int:
    """Queries the window's batches answered."""
    return rec["after"]["queries"] - rec["before"]["queries"]


def ms_per_query(rec: dict, *spans: str) -> float | None:
    """Milliseconds per query the engine and front end spent in the named
    spans (``stage_seconds``, host wall time), summed."""
    got = [delta(rec, "stage_seconds", name) for name in spans]
    q = queries(rec)
    return None if None in got or not q else 1e3 * sum(got) / q


def per_query(rec: dict, counter: str) -> float | None:
    """A ``transfer`` counter's gain per query."""
    n, q = delta(rec, "transfer", counter), queries(rec)
    return None if n is None or not q else n / q


def ratio(rec: dict, num: str, den: str) -> float | None:
    """The gain of one ``transfer`` counter over another's."""
    n, d = delta(rec, "transfer", num), delta(rec, "transfer", den)
    return None if n is None or not d else n / d
