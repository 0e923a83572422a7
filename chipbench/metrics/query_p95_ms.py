"""The 95th percentile (nearest rank), over every request due in the
window, of due time to full response at the client.  A request that
failed or never came counts as answered when the client gave up."""

import math


def read(rec: dict) -> float | None:
    lat = sorted((r["done"] if r["status"] == 200 else rec["giveup"]) -
                 r["due"] for r in rec["requests"])
    if not lat:
        return None
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
