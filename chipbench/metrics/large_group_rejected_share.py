"""Share of the groups swept on the host that the exact test in front of
the sweep rejects as holding no cell covered >= ⌈kθ⌉ times
(``host_large_rejected`` over ``host_large_groups``,
``transfer_stats()``)."""

from chipbench.window import ratio


def read(rec: dict) -> float | None:
    return ratio(rec, "host_large_rejected", "host_large_groups")
