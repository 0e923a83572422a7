"""Engine milliseconds per query in the "probe" stage
(``stage_seconds["probe"]`` over the window, host wall time)."""


def read(rec: dict) -> float | None:
    q = rec["after"]["queries"] - rec["before"]["queries"]
    s = (rec["after"]["stage_seconds"]["probe"] -
         rec["before"]["stage_seconds"]["probe"])
    return 1e3 * s / q if q else None
