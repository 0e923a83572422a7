"""Windows per group swept on the host (``host_large_windows`` over
``host_large_groups``, ``transfer_stats()``)."""

from chipbench.window import ratio


def read(rec: dict) -> float | None:
    return ratio(rec, "host_large_windows", "host_large_groups")
