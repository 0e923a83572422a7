"""Mean milliseconds a query waited in the batcher's queue before its
batch was dispatched (``stage_seconds["queue_wait"]`` over the window)."""


def read(rec: dict) -> float | None:
    q = rec["after"]["queries"] - rec["before"]["queries"]
    w = (rec["after"]["stage_seconds"]["queue_wait"] -
         rec["before"]["stage_seconds"]["queue_wait"])
    return 1e3 * w / q if q else None
