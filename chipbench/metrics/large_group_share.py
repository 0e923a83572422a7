"""Share of the kept (query, document) groups (at least ⌈kθ⌉ distinct
coordinates) that have more than 32 windows and so are swept on the host
(``host_large_groups`` over ``groups_kept``, ``transfer_stats()``)."""

from chipbench.window import ratio


def read(rec: dict) -> float | None:
    return ratio(rec, "host_large_groups", "groups_kept")
