"""Engine milliseconds per query in the "sketch" stage
(``stage_seconds["sketch"]`` over the window, host wall time)."""


def read(rec: dict) -> float | None:
    q = rec["after"]["queries"] - rec["before"]["queries"]
    s = (rec["after"]["stage_seconds"]["sketch"] -
         rec["before"]["stage_seconds"]["sketch"])
    return 1e3 * s / q if q else None
