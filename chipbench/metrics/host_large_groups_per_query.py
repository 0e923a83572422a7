"""Matched (query, document) groups of more than 32 windows, which the
device plan sweeps on the host, per query (``transfer_stats()``)."""


def read(rec: dict) -> float | None:
    q = rec["after"]["queries"] - rec["before"]["queries"]
    g = (rec["after"]["transfer"]["host_large_groups"] -
         rec["before"]["transfer"]["host_large_groups"])
    return g / q if q else None
