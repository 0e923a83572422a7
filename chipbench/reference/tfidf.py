"""tf-idf weighted Jaccard: Improved Consistent Weighted Sampling (Ioffe).

The weight of a token t that occurs x times in a text is
w(t, x) = x * idf(t), with the smooth idf of the whole corpus,
idf(t) = ln((N + N_t) / N_t) + 1, where N is the number of documents and
N_t the number that hold t (at least 1).  Coordinate c samples, per token,
r, s ~ Gamma(2, 1) and beta ~ Uniform(0, 1) from splitmix64 of
(seed_c, t), and maps (t, w) to

    kq = floor(ln w / r + beta),   a = s / (exp(r (kq - beta)) exp(r)).

A text's min-hash on c is the token with the least a, identified by
(t, kq); two min-hashes are equal when both parts are.  Raising x never
lowers kq, so a token's a only falls as it repeats.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

_GAMMA = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(z):
    z = np.asarray(z, np.uint64)
    with np.errstate(over="ignore"):
        z = z + _GAMMA
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _mix2(a, b):
    with np.errstate(over="ignore"):
        return _splitmix64(_splitmix64(a) ^
                           (np.asarray(b, np.uint64) * _GAMMA))


def _unit(bits):
    """uint64 -> float64 in (0, 1): the top 53 bits plus half a unit."""
    return ((bits >> np.uint64(11)).astype(np.float64) + 0.5) * 2.0 ** -53


class Scheme:
    def __init__(self, cfg: dict, docs: list[np.ndarray]):
        if cfg.get("tf", "raw") != "raw" or cfg.get("idf", "smooth") != \
                "smooth":
            raise ValueError("the reference knows raw tf and smooth idf only")
        self.k = cfg["k"]
        vocab = cfg["corpus"]["vocab"]
        df = np.zeros(vocab, np.int64)
        for d in docs:
            df[np.unique(d)] += 1
        nt = np.maximum(df, 1).astype(np.float64)
        self.idf = np.log((float(len(docs)) + nt) / nt) + 1.0
        seeds = _mix2(np.uint64(cfg["hash_seed"]),
                      np.arange(self.k, dtype=np.uint64))
        base = _mix2(seeds[:, None], np.arange(vocab, dtype=np.uint64)[None])
        u = [_unit(_mix2(base, np.uint64(i))) for i in range(1, 6)]
        self.r = -np.log(u[0] * u[1])                         # (k, vocab)
        self.s = -np.log(u[2] * u[3])
        self.beta = u[4]

    def _parts(self, tokens: np.ndarray, counts: np.ndarray):
        w = np.maximum(counts.astype(np.float64) * self.idf[tokens], 1e-300)
        r, beta = self.r[:, tokens], self.beta[:, tokens]
        kq = np.floor(np.log(w)[None, :] / r + beta)
        a = self.s[:, tokens] / (np.exp(r * (kq - beta)) * np.exp(r))
        return kq.astype(np.int64), a

    def values(self, tokens: np.ndarray, ranks: np.ndarray):
        kq, a = self._parts(tokens, ranks)
        return tokens, kq, a

    def target(self, query: np.ndarray):
        toks, counts = np.unique(query, return_counts=True)
        kq, a = self._parts(toks, counts)
        best = a.argmin(axis=1)
        rows = np.arange(self.k)
        return SimpleNamespace(tokens=toks[best], kq=kq[rows, best][:, None],
                               a=a[rows, best][:, None])

    @staticmethod
    def compare(vals, target):
        tokens, kq, a = vals
        eq = (tokens[None, :] == target.tokens[:, None]) & (kq == target.kq)
        return a < target.a, eq
