"""Engine milliseconds per query in the "sweep" stage
(``stage_seconds["sweep"]`` over the window, host wall time)."""


def read(rec: dict) -> float | None:
    q = rec["after"]["queries"] - rec["before"]["queries"]
    s = (rec["after"]["stage_seconds"]["sweep"] -
         rec["before"]["stage_seconds"]["sweep"])
    return 1e3 * s / q if q else None
