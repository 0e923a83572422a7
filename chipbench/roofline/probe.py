"""The arena probe's least bytes, from its shapes alone.

One probe call looks up P = B * k keys (B queries of k sketch
coordinates) in the resident arena and returns each key's CSR extent.
Whatever the search does, it must at least

* take the P probe keys and their valid flags up: a key is two u32 words
  in a packed arena, three in a coord arena (the coordinate's tag word);
* read one arena slot per probe: the slot's key words and its two CSR
  offsets (i32);
* give the P extents (start, end; i32 each) back.

The binary search's steps are not counted, so a better search can never
read over 100%.  The least time is these bytes over the chip's HBM
bandwidth: the probe is bound by bandwidth, not by operations.
"""

from __future__ import annotations

WORD = 4                       # u32 key words, i32 offsets and extents
KEY_WORDS = {"packed": 2, "coord": 3}


def least_bytes(P: int, mode: str) -> int:
    """Bytes any implementation of one probe call of P keys must move."""
    key = KEY_WORDS[mode] * WORD
    up = P * (key + 1)                     # keys and bool valid flags
    arena = P * (key + 2 * WORD)           # one slot and its two offsets
    down = P * 2 * WORD                    # start and end
    return up + arena + down


def least_seconds(Ps, mode: str, peak: dict) -> float:
    """The least time of the probe calls of sizes ``Ps`` on a chip whose
    peaks are ``peak`` (an entry of ``chipbench/peaks.json``)."""
    return sum(least_bytes(P, mode) for P in Ps) / peak["hbm_bytes_per_s"]
