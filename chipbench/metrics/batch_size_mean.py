"""Queries per dispatched batch in the window (the serve batcher's
batch-size count and sum, ``repro.serve.metrics``)."""


def read(rec: dict) -> float | None:
    b = rec["after"]["batches"] - rec["before"]["batches"]
    q = rec["after"]["queries"] - rec["before"]["queries"]
    return q / b if b else None
