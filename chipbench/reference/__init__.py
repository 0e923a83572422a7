"""The plain reference: Definition 1 answered straight from the corpus.

Independent of the program: it imports nothing of it and reads nothing it
made (no store, no weights, no hash tables).  It takes the corpus the
configuration draws (``chipbench.workload.make_corpus``) and the sketch
scheme the configuration names (``chipbench/reference/<similarity>.py``),
and answers a query by the definition:

    T[i..j] of document d is a result  iff  at least m = ceil(k * theta)
    of the k sketch coordinates give T[i..j] the query's min-hash.

A scheme gives, for coordinate c and a token that occurs x times in a
subsequence, a value; the subsequence's min-hash on c is decided by these
values alone.  Per coordinate and per token of a document the scheme
marks, by occurrence rank r (the token's r-th occurrence in the
subsequence), whether the value is below the query's (``lt``: from then on
the subsequence's minimum lies below the query's) or equal to it (``eq``:
the subsequence reaches the query's min-hash).  Both are monotone in r, so
for a start i the subsequence T[i..j] has the query's min-hash on c
exactly for

    eq_pos(c, i) <= j < bad_pos(c, i),

the first position at which some token reaches its first ``eq`` and its
first ``lt`` occurrence counted from i.  The answer of a document is then,
row by row, the maximal runs of j covered by at least m such intervals,
and its similarity estimate the number of coordinates with any interval,
over k.  No windows, no index: O(k n) per document and query, and only for
documents that hold the query's min-hash token on at least m coordinates.
"""

from __future__ import annotations

import importlib
import math

import numpy as np


def load_scheme(cfg: dict, docs: list[np.ndarray]):
    """The scheme module named by the configuration's ``similarity``."""
    mod = importlib.import_module(f"{__name__}.{cfg['similarity']}")
    return mod.Scheme(cfg, docs)


class Reference:
    """Definition-1 answers over ``docs`` under ``scheme``.

    ``need`` is the number of coordinates a result must share with the
    query: ``ceil(k * theta)`` by default.  The control sets it one lower.
    """

    def __init__(self, docs: list[np.ndarray], scheme, theta: float,
                 need: int | None = None):
        self.docs = docs
        self.scheme = scheme
        self.k = scheme.k
        self.need = need if need is not None else max(
            1, math.ceil(self.k * theta))
        # token -> the documents that hold it (sorted (token, doc) pairs)
        tok = np.concatenate([np.unique(d) for d in docs])
        doc = np.repeat(np.arange(len(docs)),
                        [len(np.unique(d)) for d in docs])
        order = np.lexsort((doc, tok))
        self._tok, self._doc = tok[order], doc[order]
        self._prep: dict[int, tuple] = {}

    def _docs_with(self, token: int) -> np.ndarray:
        lo, hi = np.searchsorted(self._tok, [token, token + 1])
        return self._doc[lo:hi]

    def _candidates(self, target) -> np.ndarray:
        """The documents that hold the query's min-hash token on at least
        ``need`` coordinates: only these can hold a result."""
        hits = np.concatenate([self._docs_with(int(t))
                               for t in target.tokens])
        return np.flatnonzero(np.bincount(hits, minlength=len(self.docs))
                              >= self.need)

    def candidates(self, query: np.ndarray) -> int:
        """How many documents :meth:`answer` has to look at for ``query``:
        the work a query asks of the index grows with it."""
        return len(self._candidates(
            self.scheme.target(np.asarray(query, np.int64))))

    def _prepared(self, d: int) -> tuple:
        """Document d grouped by token: (positions sorted by (token,
        position), group of each, first index of each group, group
        lengths, rank within the group, the scheme's values)."""
        got = self._prep.get(d)
        if got is None:
            toks = self.docs[d]
            perm = np.argsort(toks, kind="stable")
            ts = toks[perm]
            new = np.ones(len(ts), bool)
            new[1:] = ts[1:] != ts[:-1]
            gstart = np.flatnonzero(new)
            gid = np.cumsum(new) - 1
            glen = np.diff(np.append(gstart, len(ts)))
            rank = np.arange(len(ts)) - gstart[gid]
            got = (perm, gid, gstart, glen, rank,
                   self.scheme.values(ts, rank + 1))
            self._prep[d] = got
        return got

    def answer(self, query: np.ndarray) -> dict[int, tuple]:
        """{doc id: (coordinates hit, rows)} for every document with a
        result; rows is an int64 (R, 3) array of (i, j_lo, j_hi) maximal
        runs, sorted."""
        target = self.scheme.target(np.asarray(query, np.int64))
        out = {}
        for d in self._candidates(target):
            got = self._document(int(d), target)
            if got is not None:
                out[int(d)] = got
        return out

    def _document(self, d: int, target):
        perm, gid, gstart, glen, rank, vals = self._prepared(d)
        n = len(perm)
        lt, eq = self.scheme.compare(vals, target)          # (k, n) each
        bad = self._reach(lt, perm, gid, gstart, glen, rank, n)
        reach = self._reach(eq, perm, gid, gstart, glen, rank, n)
        valid = reach < bad                                 # (k, n) by i
        hit = int(valid.any(axis=1).sum())
        if hit < self.need:
            return None
        rows = np.flatnonzero(valid.sum(axis=0) >= self.need)
        if not len(rows):
            return None
        runs = _runs(reach[:, rows], bad[:, rows], valid[:, rows], rows,
                     self.need)
        if not len(runs):
            return None
        return hit, runs

    @staticmethod
    def _reach(mark, perm, gid, gstart, glen, rank, n) -> np.ndarray:
        """(k, n): for each start i, the first position j at which some
        token reaches the first ``mark``-ed rank of its group, counting
        occurrences from i; n where none does."""
        k = mark.shape[0]
        first = np.minimum.reduceat(np.where(mark, rank, n + 1), gstart,
                                    axis=1)[:, gid]          # (k, n) sorted
        tgt = rank + first                                  # rank reached
        ok = tgt < glen[gid]
        at = np.where(ok, np.arange(n) + first, 0)
        by_sorted = np.where(ok, perm[np.minimum(at, n - 1)], n)
        by_pos = np.empty((k, n), np.int64)
        by_pos[:, perm] = by_sorted
        return np.minimum.accumulate(by_pos[:, ::-1], axis=1)[:, ::-1]


def _runs(lo, hi, valid, rows, need: int) -> np.ndarray:
    """Maximal runs of j covered by >= ``need`` of the intervals
    [lo, hi) of each row (columns of the (k, R) arrays)."""
    k, R = lo.shape
    pos = np.concatenate([np.where(valid, lo, -1), np.where(valid, hi, -1)]
                         ).T                                 # (R, 2k)
    delta = np.concatenate([np.where(valid, 1, 0), np.where(valid, -1, 0)]
                           ).T
    order = np.argsort(pos, axis=1, kind="stable")
    pos = np.take_along_axis(pos, order, axis=1)
    count = np.cumsum(np.take_along_axis(delta, order, axis=1), axis=1)
    nxt = np.empty_like(pos)
    nxt[:, :-1] = pos[:, 1:]
    nxt[:, -1] = pos[:, -1]
    # the segment [pos, nxt) after each event; drop empty ones, keep hot
    keep = nxt > pos
    seg_row = np.broadcast_to(np.arange(R)[:, None], pos.shape)[keep]
    s, e, hot = pos[keep], nxt[keep], count[keep] >= need
    if not hot.any():
        return np.empty((0, 3), np.int64)
    # segments of a row tile it: a run starts at a hot segment whose
    # predecessor in the row is cold, and ends where the next one is
    prev_hot = np.zeros_like(hot)
    prev_hot[1:] = hot[:-1] & (seg_row[1:] == seg_row[:-1])
    next_hot = np.zeros_like(hot)
    next_hot[:-1] = hot[1:] & (seg_row[1:] == seg_row[:-1])
    starts = np.flatnonzero(hot & ~prev_hot)
    ends = np.flatnonzero(hot & ~next_hot)
    return np.stack([rows[seg_row[starts]], s[starts], e[ends] - 1],
                    axis=1).astype(np.int64)


def block_rows(blocks) -> np.ndarray:
    """A served match's blocks (i_lo, i_hi, j_lo, j_hi) as sorted maximal
    (i, j_lo, j_hi) runs, merging runs of one row that touch."""
    if not blocks:
        return np.empty((0, 3), np.int64)
    b = np.asarray(blocks, np.int64)
    n = b[:, 1] - b[:, 0] + 1
    i = np.repeat(b[:, 0], n) + (np.arange(n.sum()) -
                                 np.repeat(np.cumsum(n) - n, n))
    jl, jh = np.repeat(b[:, 2], n), np.repeat(b[:, 3], n)
    order = np.lexsort((jl, i))
    i, jl, jh = i[order], jl[order], jh[order]
    out = []
    for r, lo, hi in zip(i.tolist(), jl.tolist(), jh.tolist()):
        if out and out[-1][0] == r and lo <= out[-1][2] + 1:
            out[-1][2] = max(out[-1][2], hi)
        else:
            out.append([r, lo, hi])
    return np.asarray(out, np.int64)
