"""Unweighted multiset Jaccard: min-hash over (token, occurrence) pairs.

The configuration's hash family is the universal one of the MONO paper
(section 2.2), h_c(t, x) = (a1_c * t + a2_c * x + b_c) mod (2**61 - 1), with
the coefficients of coordinate c drawn by splitmix64 from the
configuration's ``hash_seed``.  A subsequence's min-hash on c is the least
h_c(t, x) over its tokens t and x = 1 .. (occurrences of t in it); two
min-hashes are equal when their hash values are.  Here the arithmetic is
done on Python integers (the coefficients, and one table per coordinate
over the vocabulary and over occurrence counts), then summed in uint64,
where three values below 2**61 cannot overflow.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

P61 = (1 << 61) - 1
M64 = (1 << 64) - 1


def splitmix64(z: int) -> int:
    z = (z + 0x9E3779B97F4A7C15) & M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & M64
    return z ^ (z >> 31)


def mix2(a: int, b: int) -> int:
    return splitmix64(splitmix64(a) ^ ((b * 0x9E3779B97F4A7C15) & M64))


class Scheme:
    def __init__(self, cfg: dict, docs: list[np.ndarray]):
        if cfg.get("family", "universal") != "universal":
            raise ValueError("the reference knows the universal family only")
        self.k = cfg["k"]
        vocab = cfg["corpus"]["vocab"]
        top = max(max(len(d) for d in docs), cfg["corpus"]["doc_len"][1],
                  4096) + 1
        ts, xs = range(vocab), range(top)
        self.t_table, self.x_table, self.b = [], [], []
        for c in range(self.k):
            base = mix2(cfg["hash_seed"], c)
            a1 = splitmix64(base ^ 0xA1) % P61 or 1
            a2 = splitmix64(base ^ 0xA2) % P61 or 1
            self.b.append(splitmix64(base ^ 0xB0) % P61)
            self.t_table.append([a1 * t % P61 for t in ts])
            self.x_table.append([a2 * x % P61 for x in xs])
        self.t_table = np.asarray(self.t_table, np.uint64)    # (k, vocab)
        self.x_table = np.asarray(self.x_table, np.uint64)    # (k, top)
        self.b = np.asarray(self.b, np.uint64)[:, None]

    def _h(self, tokens: np.ndarray, counts: np.ndarray) -> np.ndarray:
        """(k, n) hash values h_c(tokens, counts), uint64."""
        return (self.t_table[:, tokens] + self.x_table[:, counts] +
                self.b) % np.uint64(P61)

    def values(self, tokens: np.ndarray, ranks: np.ndarray) -> np.ndarray:
        return self._h(tokens, ranks)

    def target(self, query: np.ndarray):
        toks, counts = np.unique(query, return_counts=True)
        t = np.repeat(toks, counts)
        x = np.arange(len(t)) - np.repeat(np.cumsum(counts) - counts,
                                          counts) + 1
        h = self._h(t, x)
        best = h.argmin(axis=1)
        return SimpleNamespace(tokens=t[best], value=h.min(axis=1)[:, None])

    @staticmethod
    def compare(vals: np.ndarray, target):
        return vals < target.value, vals == target.value
