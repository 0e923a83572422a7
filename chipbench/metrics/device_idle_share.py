"""1 - (union of device-operation intervals) / (traced window), from the
profiler trace; nothing without one."""


def read(rec: dict) -> float | None:
    t = rec.get("trace")
    return None if t is None else t["idle_share"]
