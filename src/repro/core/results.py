"""Typed query results and options — the public result surface and the
serving wire protocol.

The engine's native result is :class:`repro.core.query.Alignment` (one per
(query, data-text) pair, carrying the Definition-1 maximal blocks).  The
facade and the network server speak in terms of:

* :class:`Match` — one aligned data text, as a frozen record with the
  global ``doc_id``, the outer ``span`` of all result subsequences in the
  data text, the ``query_span`` it aligned against (Definition 1 aligns
  the *whole* query, so this is the full query extent), the
  ``estimated_similarity`` (the fraction of the query's k sketch
  coordinates that collided with the text — ``>= theta`` for every
  returned match, Eq. 2/Eq. 5), and the full ``blocks`` family.
* :class:`QueryResult` — the per-query container; iterates its matches
  (so ``for hit in aligner.find(...)`` keeps working) and round-trips
  through ``to_dict``/``from_dict``/JSON, which is exactly the payload
  the :mod:`repro.serve` server puts on the wire.
* :class:`QueryOptions` — one dataclass for the query-execution knobs
  that used to sprawl across ``backend``/``fanout``/``sketches`` keyword
  arguments.  ``Aligner.find/find_batch``,
  ``LiveIndex.batch_query`` and ``ShardedAlignmentIndex.batch_query`` all
  accept ``options=QueryOptions(...)``; the old kwargs still work for one
  release behind a ``DeprecationWarning`` (:func:`coerce_query_options`).

None of these affect result *content*: every options combination remains
block-identical, and a ``Match`` is a re-labelling of an ``Alignment``.
"""

from __future__ import annotations

import json
import warnings
from dataclasses import dataclass, field, replace

__all__ = ["Match", "QueryResult", "QueryOptions", "UNSET",
           "coerce_query_options"]


@dataclass(frozen=True)
class Match:
    """One aligned data text (all its result subsequences, as blocks).

    span: (lo, hi) outer extent of the result subsequences in the data
        text: every reported ``T[i..j]`` has ``lo <= i`` and ``j <= hi``.
    query_span: (0, len(query) - 1) — the query extent the text aligned
        against (the paper aligns the full query).
    estimated_similarity: colliding-coordinate fraction ``ncoords / k``
        (>= theta by construction: a reported cell is covered by
        >= ceil(k * theta) coordinates); ``None`` when the producing path
        did not count collisions.
    blocks: the Definition-1 maximal blocks, ``(i_lo, i_hi, j_lo, j_hi)``
        tuples exactly as :class:`~repro.core.query.Alignment` carries
        them (every ``T[i..j]`` with ``i in [i_lo, i_hi]``,
        ``j in [j_lo, j_hi]`` is a result).
    """

    doc_id: int
    span: tuple[int, int]
    query_span: tuple[int, int]
    estimated_similarity: float | None
    blocks: list[tuple[int, int, int, int]] = field(default_factory=list)

    @property
    def text_id(self) -> int:
        """Legacy alias (``Alignment.text_id``) so pre-typed callers keep
        reading ``hit.text_id``."""
        return self.doc_id

    def __iter__(self):
        # tuple-style unpacking: doc_id, span, query_span, similarity
        yield self.doc_id
        yield self.span
        yield self.query_span
        yield self.estimated_similarity

    def to_dict(self) -> dict:
        return {"doc_id": self.doc_id,
                "span": list(self.span),
                "query_span": list(self.query_span),
                "estimated_similarity": self.estimated_similarity,
                "blocks": [list(b) for b in self.blocks]}

    @classmethod
    def from_dict(cls, d: dict) -> "Match":
        return cls(doc_id=int(d["doc_id"]),
                   span=tuple(int(x) for x in d["span"]),
                   query_span=tuple(int(x) for x in d["query_span"]),
                   estimated_similarity=(
                       None if d.get("estimated_similarity") is None
                       else float(d["estimated_similarity"])),
                   blocks=[tuple(int(x) for x in b) for b in d["blocks"]])

    @classmethod
    def from_alignment(cls, al, *, k: int, query_len: int) -> "Match":
        """Re-label one engine :class:`Alignment` (``k`` is the sketch
        width, for the similarity estimate)."""
        blocks = list(al.blocks)
        span = (min(b[0] for b in blocks), max(b[3] for b in blocks))
        sim = None if al.ncoords is None else al.ncoords / k
        return cls(doc_id=int(al.text_id), span=span,
                   query_span=(0, max(0, query_len - 1)),
                   estimated_similarity=sim, blocks=blocks)


@dataclass(frozen=True)
class QueryResult:
    """All matches of one query, plus the query's own context.

    Iterates (and indexes, and bool-tests) as the list of matches, so the
    pre-typed ``for hit in aligner.find(q, theta)`` loop is unchanged.

    ``degraded=True`` marks a *partial* result: one or more sharded
    fan-out probes failed (after bounded retries) and were skipped, so
    matches from the shards in ``failed_shards`` may be missing.  Healthy
    results keep the defaults, so pre-degraded consumers are unaffected.
    """

    matches: list[Match]
    theta: float
    query_len: int | None = None
    degraded: bool = False
    failed_shards: tuple = ()

    def __iter__(self):
        return iter(self.matches)

    def __len__(self) -> int:
        return len(self.matches)

    def __getitem__(self, i):
        return self.matches[i]

    def __bool__(self) -> bool:
        return bool(self.matches)

    def to_dict(self) -> dict:
        return {"matches": [m.to_dict() for m in self.matches],
                "theta": self.theta, "query_len": self.query_len,
                "degraded": self.degraded,
                "failed_shards": list(self.failed_shards)}

    @classmethod
    def from_dict(cls, d: dict) -> "QueryResult":
        return cls(matches=[Match.from_dict(m) for m in d["matches"]],
                   theta=float(d["theta"]),
                   query_len=(None if d.get("query_len") is None
                              else int(d["query_len"])),
                   degraded=bool(d.get("degraded", False)),
                   failed_shards=tuple(d.get("failed_shards", ())))

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @classmethod
    def from_json(cls, s: str) -> "QueryResult":
        return cls.from_dict(json.loads(s))

    @classmethod
    def from_alignments(cls, alignments, *, theta: float, k: int,
                        query_len: int) -> "QueryResult":
        return cls(matches=[Match.from_alignment(al, k=k,
                                                 query_len=query_len)
                            for al in alignments],
                   theta=theta, query_len=query_len)


# sentinel distinguishing "kwarg not passed" from an explicit None
UNSET = object()

#: release in which the deprecated per-stage kwargs are removed — named in
#: every DeprecationWarning so callers know how long the shim lives
_REMOVAL_RELEASE = "0.3"

# Legacy kwargs that RENAME to a QueryOptions field.  Kwargs whose spelling
# already matches the field (sketch_backend=, ...) live only in
# _LEGACY_PASSTHROUGH — a name is either current or legacy, never both.
_LEGACY_RENAMES = {"backend": "sketch_backend"}

# legacy kwargs whose QueryOptions field keeps the same name
_LEGACY_PASSTHROUGH = ("sketch_backend", "fanout", "sketches")

#: the fields a plan resolves (the keys of repro.core.plan._CHOICES,
#: repeated here so the wire/result layer stays import-light)
_PLAN_FIELDS = ("sketch_backend", "fanout")

_WIRE_FIELDS = ("plan",) + _PLAN_FIELDS


@dataclass(frozen=True)
class QueryOptions:
    """Execution knobs for the batched query path (content-neutral: every
    combination returns block-identical results).

    plan: which :class:`repro.core.plan.ExecutionPlan` runs the batch —
        ``"cpu"`` (NumPy reference path), ``"device"`` (arena resident on
        the accelerator, probe and sweep run there) or ``"auto"``
        (device when a real accelerator backs jax, else silently cpu).
        Resolved once per batch by ``repro.core.plan.resolve_plan``.
    sketch_backend / fanout: per-stage *pins*.  ``None`` (the default)
        lets the plan pick; ``sketch_backend="pallas"`` moves weighted
        sketching into the f32 device kernel, ``fanout="serial"`` probes
        a sharded store's shards one after another.  A value the plan
        cannot execute raises ``TypeError`` at resolution.
    sketches: precomputed batch sketch coordinates, short-circuiting the
        sketch stage (the caller guarantees they match the queries).
        Excluded from the wire form.
    """

    plan: str = "cpu"
    sketch_backend: str | None = None
    fanout: str | None = None
    sketches: object = None

    def batch_key(self) -> tuple:
        """Coalescing key: requests whose options agree on these knobs may
        be served by one fused probe.  The plan name is part of the key,
        so mixed-plan traffic (cpu and device requests interleaved on one
        server) never coalesces into a single dispatch; unresolved pins
        (``None``) key differently from their resolved values — a
        conservative split that can only under-coalesce, never mix."""
        return (self.plan, self.sketch_backend, self.fanout)

    def to_dict(self) -> dict:
        d = {"plan": self.plan}
        d.update({f: getattr(self, f) for f in _PLAN_FIELDS
                  if getattr(self, f) is not None})
        return d

    @classmethod
    def from_dict(cls, d: dict | None) -> "QueryOptions":
        d = d or {}
        unknown = set(d) - set(_WIRE_FIELDS)
        if unknown:
            if "sketches" in d:
                raise ValueError("sketches are an in-process short-circuit "
                                 "and cannot travel over the wire")
            raise ValueError(f"unknown query options: {sorted(unknown)}")
        return cls(**{k: d[k] for k in d})


def coerce_query_options(options: QueryOptions | None, where: str,
                         **legacy) -> QueryOptions:
    """Resolve the (new options object, old kwargs) call surface into one
    :class:`QueryOptions`.

    ``legacy`` maps old kwarg names to the values the caller received
    (``UNSET`` when not passed).  Passing any old kwarg emits a
    ``DeprecationWarning`` naming the replacement and the release the
    kwarg dies in; mixing both surfaces in one call is an error (silently
    preferring one would hide a bug).  Coerced stage kwargs become *pins*
    on the default ``"cpu"`` plan, which reproduces their pre-plan
    behavior exactly.
    """
    given = {k: v for k, v in legacy.items() if v is not UNSET}
    if not given:
        return options if options is not None else QueryOptions()
    if options is not None:
        raise TypeError(
            f"{where}: pass options=QueryOptions(...) or the legacy "
            f"keyword arguments {sorted(given)}, not both")
    renames = {}
    for k in given:
        if k in _LEGACY_RENAMES:
            renames[k] = _LEGACY_RENAMES[k]
        elif k in _LEGACY_PASSTHROUGH:
            renames[k] = k
        else:
            raise TypeError(f"{where}: unknown legacy keyword argument {k!r}")
    warnings.warn(
        f"{where}: keyword arguments {sorted(given)} are deprecated and "
        f"will be removed in release {_REMOVAL_RELEASE}; pass "
        "options=QueryOptions(" +
        ", ".join(f"{renames[k]}=..." for k in sorted(given)) + ") instead",
        DeprecationWarning, stacklevel=3)
    return replace(QueryOptions(), **{renames[k]: v for k, v in given.items()})
