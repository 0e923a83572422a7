"""Live incremental serving: frozen mmap shards + a mutable delta index,
folded together by columnar merge-compaction into new store generations.

The hash-based framework indexes a *static* corpus, but a production
service takes writes while it serves.  Because CWS samplings are
consistent per subsequence, a document sketched once never needs
re-sketching — so a :class:`LiveIndex` pairs the serving halves that
already exist:

* ``frozen`` — an mmap-backed :class:`~repro.core.search.SearchIndex`
  (plus its fused :class:`~repro.core.frozen.ProbeArena`), loaded from a
  versioned store directory;
* ``delta``  — a small mutable :class:`~repro.core.builder.IndexBuilder`
  that absorbs ``add_text`` writes between compactions.

Queries merge deterministically: the batch pipeline
(``repro.core.query.run_batch``) probes each level (an arena probe of the
frozen index, a dict probe of the delta) and sweeps it, delta text ids
re-based after the frozen corpus.  That is block-identical to a
from-scratch build of the same corpus: every text id belongs to exactly
one level, so each (query, text) sweep group comes entirely from one
level and keeps its coordinate-ascending order.  Results are remapped to
*global* doc ids through ``doc_map`` (the store manifest's mapping,
extended by live adds), so sharded serving keeps one id space.

``compact()`` folds the delta in: the frozen CSR tables unpack straight
back into append columns (``FrozenTable.ident_columns``), the delta's
dict tables export theirs (``IndexBuilder.table_columns``), and the
columnar pipeline freezes the concatenation — one stable sort per table,
zero re-sketching — streaming into a NEW ``v{N:06d}`` generation
directory via ``store.IndexWriter``.  Promotion is atomic and ordered
(arrays → manifest → ``CURRENT`` pointer flip), the old generation stays
on disk for rollback, and readers flip via
:func:`repro.core.store.resolve_store`.

``LiveIndex.query``/``batch_query`` return global-id results (like
``ShardedAlignmentIndex``); the module-level query functions, handed a
``LiveIndex`` directly, work in its local id space.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from ..wal import WalConfig, WriteAheadLog, wal_dir
from . import store as index_store
from .builder import IndexBuilder
from .guard import engine_only
from .query import Alignment, query as _query, run_batch
from .results import UNSET, QueryOptions, coerce_query_options
from .search import SearchIndex


@dataclass
class LiveIndex:
    """A frozen serving index that accepts writes without thawing.

    Local text id order is ``frozen`` ids first, then ``sealed`` (a delta
    level snapshotted by an in-progress overlapped compaction), then the
    active ``delta`` — and it is STABLE across promotion: when a merged
    frozen+sealed generation is promoted, the sealed texts keep the same
    local ids (now inside the new frozen) and the active delta keeps its
    offsets, so in-flight queries and compactions never see ids move.
    """

    frozen: SearchIndex
    delta: IndexBuilder
    doc_map: list[int]                  # local text id -> global doc id
    root: Path | None = None            # versioned store root (compact target)
    generation: int = 0                 # serving generation under ``root``
    mmap: bool = True                   # how compacted generations load back
    scheme_in_manifest: bool = True     # sharded shards omit the scheme spec
    sealed: IndexBuilder | None = None  # delta level an overlapped compaction
    #                                     is folding in (immutable once set)
    wal: WriteAheadLog | None = None    # durable ingest log (opt-in)
    _sealed_docs: list[int] = field(default_factory=list, init=False,
                                    repr=False)
    _next_gid: int = field(default=0, init=False, repr=False)
    # monotonic timestamp of the first add into the current delta (None
    # while it is empty) — the supervisor's age-based compaction trigger
    _delta_born: float | None = field(default=None, init=False, repr=False)
    # request-id -> local text id, for at-least-once clients: a retried
    # /add with the same id returns the original doc instead of indexing
    # a duplicate.  Entries live for the un-compacted window (dropped once
    # their doc folds into a promoted generation) and are rebuilt from the
    # WAL on replay, so the window survives a crash.
    _requests: dict[str, int] = field(default_factory=dict, init=False,
                                      repr=False)
    _dedup_hits: int = field(default=0, init=False, repr=False)
    wal_replayed: int = field(default=0, init=False, repr=False)
    # WAL positions: _wal_covered is the serving generation's watermark
    # (records below it are folded in); _sealed_watermark is the pending
    # one an in-flight overlapped compaction will promote
    _wal_covered: int = field(default=0, init=False, repr=False)
    _sealed_watermark: int | None = field(default=None, init=False,
                                          repr=False)

    def __post_init__(self):
        self._next_gid = max(self.doc_map, default=-1) + 1

    # -- construction -------------------------------------------------------

    @classmethod
    def open(cls, root, *, mmap: bool = True, scheme=None,
             wal: "bool | WalConfig" = False) -> "LiveIndex":
        """Open a store directory for live serving: mmap-load the serving
        generation, start an empty delta, and adopt the manifest's
        ``doc_map`` (identity when the store never recorded one).

        Resolution goes through :func:`~repro.core.store.resolve_verified`
        — a serving generation that fails its checksum verification is
        quarantined and the newest verifying generation is served instead
        (recovery happens here, at open time; queries never re-verify).

        ``wal`` (``True`` or a :class:`~repro.wal.WalConfig`) makes ingest
        durable: adds append to ``<root>/wal/`` before indexing, and this
        open REPLAYS every un-compacted record into the fresh delta —
        idempotent, because records below the manifest's ``wal_watermark``
        or whose gid the ``doc_map`` already holds are skipped, so
        replaying twice equals replaying once.
        """
        root = Path(root)
        serve_dir = index_store.resolve_verified(root)
        # resolve_verified already checksum-verified serve_dir
        frozen = index_store.load_index(serve_dir, mmap=mmap, scheme=scheme,
                                        verify=False)
        manifest = index_store.read_manifest(serve_dir)
        doc_map = manifest.get("doc_map") or list(range(frozen.num_texts))
        live = cls(frozen=frozen,
                   delta=IndexBuilder(scheme=frozen.scheme,
                                      method=frozen.method),
                   doc_map=[int(g) for g in doc_map], root=root,
                   generation=index_store.current_generation(root),
                   mmap=mmap,
                   scheme_in_manifest=manifest.get("scheme") is not None)
        if wal:
            watermark = int(manifest.get("wal_watermark") or 0)
            live.wal = WriteAheadLog(
                wal_dir(root),
                config=wal if isinstance(wal, WalConfig) else None,
                start_lsn=watermark)
            live._wal_covered = watermark
            known = set(live.doc_map)
            for rec in live.wal.records():
                if rec.lsn < watermark or rec.gid in known:
                    continue            # already folded into the frozen gen
                live._apply_add(rec.tokens, gid=rec.gid,
                                request_id=rec.request_id)
                live.wal_replayed += 1
        return live

    # -- query-engine surface -----------------------------------------------

    @property
    def scheme(self):
        return self.frozen.scheme

    @property
    def method(self) -> str:
        return self.frozen.method

    @property
    def is_frozen(self) -> bool:
        return False            # accepts adds (the whole point)

    @property
    def is_live(self) -> bool:
        return True             # query.probe_store dispatches on this

    def _levels(self):
        """The index levels in local-id order (frozen, sealed?, delta)."""
        if self.sealed is not None:
            return (self.frozen, self.sealed, self.delta)
        return (self.frozen, self.delta)

    @property
    def num_texts(self) -> int:
        return sum(lv.num_texts for lv in self._levels())

    @property
    def num_windows(self) -> int:
        return sum(lv.num_windows for lv in self._levels())

    @property
    def text_lengths(self) -> list[int]:
        out: list[int] = []
        for lv in self._levels():
            out.extend(lv.text_lengths)
        return out

    @property
    def delta_fraction(self) -> float:
        """Unfolded (sealed + delta) share of the corpus — the compaction
        trigger metric."""
        folded = self.frozen.num_texts
        return (self.num_texts - folded) / max(1, self.num_texts)

    @property
    def delta_age_s(self) -> float:
        """Seconds since the first add into the current delta (0.0 while
        it is empty) — the supervisor's age-based compaction trigger."""
        if self._delta_born is None or self.delta.num_texts == 0:
            return 0.0
        return time.monotonic() - self._delta_born

    def nbytes(self) -> int:
        return sum(lv.nbytes() for lv in self._levels())

    # -- writes -------------------------------------------------------------

    @engine_only
    def add_text(self, tokens, *, gid: int | None = None,
                 request_id: str | None = None) -> int:
        """Index one more document into the delta; returns its LOCAL text
        id (frozen ids come first, delta ids after — stable across
        compactions).  ``gid`` pins the global doc id (the sharded index
        assigns those); default is one past the largest id seen.

        ``request_id`` makes the add idempotent within the un-compacted
        window: a repeat of an id already indexed (including one replayed
        from the WAL after a crash) returns the original local id without
        indexing anything — the server-side half of safe client retries.

        With a WAL attached the record is appended (and group-commit
        policy applied) BEFORE the document becomes visible, so anything
        a query can see is at worst one fsync away from durable; call
        :meth:`wal_commit` for the hard acknowledgement barrier.
        """
        if request_id is not None:
            lid = self._requests.get(request_id)
            if lid is not None:
                self._dedup_hits += 1
                return lid
        tokens = np.asarray(tokens, np.int64)
        if gid is None:
            gid = self._next_gid
        if self.wal is not None:
            self.wal.append(int(gid), request_id, tokens)
            self.wal.maybe_sync()
        return self._apply_add(tokens, gid=int(gid), request_id=request_id)

    def _apply_add(self, tokens, *, gid: int,
                   request_id: str | None = None) -> int:
        """Index a document WITHOUT logging it — the shared tail of
        ``add_text`` and WAL replay (whose records are already on disk)."""
        if self.delta.num_texts == 0:
            self._delta_born = time.monotonic()
        base = self.frozen.num_texts + \
            (self.sealed.num_texts if self.sealed is not None else 0)
        lid = base + self.delta.add_text(np.asarray(tokens, np.int64))
        self.doc_map.append(int(gid))
        self._next_gid = max(self._next_gid, int(gid) + 1)
        if request_id is not None:
            self._requests[request_id] = lid
        return lid

    @engine_only
    def wal_commit(self) -> None:
        """Durability barrier for acknowledgements: fsync the WAL so every
        add so far survives power loss (no-op without a WAL, or when
        nothing is pending).  The serve path calls this once per batcher
        micro-batch — group commit with the batcher's linger window."""
        if self.wal is not None:
            self.wal.sync()

    def wal_status(self) -> dict | None:
        """Operator view of ingest durability (``None`` without a WAL):
        the log's counters plus replay/lag/dedup — ``lag_records`` is how
        many logged records the serving generation does not yet cover
        (what a crash would replay)."""
        if self.wal is None:
            return None
        st = self.wal.stats()
        st["replayed"] = self.wal_replayed
        st["dedup_hits"] = self._dedup_hits
        st["lag_records"] = max(0, self.wal.next_lsn - self._wal_covered)
        st["age_s"] = self.wal.age_s
        return st

    # -- queries ------------------------------------------------------------

    def lookup(self, i: int, v):
        """Merged postings of identity ``v``: frozen rows first, then each
        delta level's rows re-based after it (grouped by tid, as ``query``
        expects)."""
        rows = [tuple(int(x) for x in r) for r in self.frozen.lookup(i, v)]
        base = self.frozen.num_texts
        for lv in self._levels()[1:]:
            rows.extend((tid + base, a, b, c, d)
                        for (tid, a, b, c, d) in lv.lookup(i, v))
            base += lv.num_texts
        return rows

    def query_levels(self) -> list[tuple[object, int]]:
        """The non-empty levels, in local-id order, each with the local id
        of its first text: what the batch pipeline probes.  A freshly
        opened live store (an empty delta) probes its frozen level alone.
        """
        out, base = [], 0
        for lv in self._levels():
            if lv.num_texts:
                out.append((lv, base))
            base += lv.num_texts
        return out

    def query(self, tokens, theta: float) -> list[Alignment]:
        """Definition-1 alignment over frozen + deltas, in global doc ids."""
        return sorted((Alignment(text_id=self.doc_map[al.text_id],
                                 blocks=al.blocks, ncoords=al.ncoords)
                       for al in _query(self, tokens, theta)),
                      key=lambda a: a.text_id)

    def batch_query(self, texts, theta: float, *,
                    options: QueryOptions | None = None,
                    sketches=UNSET, backend=UNSET,
                    stage_times: dict | None = None) -> list[list[Alignment]]:
        """Batched :meth:`query` (the serving path): the batch pipeline
        (:func:`repro.core.query.run_batch`) over the levels, text ids
        remapped to global doc ids through ``doc_map``.

        Execution comes in as ``options=QueryOptions(...)``; the ``plan``
        field is resolved once per batch (:func:`repro.core.plan.
        resolve_plan`).  Under ``plan="device"`` the frozen level probes
        the device-resident arena while the mutable delta level keeps the
        host dict probe (live writes stay served without re-upload churn).
        The pre-redesign ``sketches``/``backend`` keywords still work
        behind a ``DeprecationWarning``.  ``stage_times`` accumulates
        per-stage wall seconds under ``"sketch"``/``"probe"``/``"sweep"``
        and their children (:mod:`repro.core.spans`) when given.
        """
        opts = coerce_query_options(options, "LiveIndex.batch_query",
                                    sketches=sketches, backend=backend)
        return run_batch(self, texts, theta, opts, stage_times,
                         ids=self.doc_map.__getitem__)

    # -- compaction ---------------------------------------------------------

    def _merged_builder(self, *, levels=None):
        """The given levels (default: all of them), absorbed into one
        columnar builder — block-identical to a from-scratch build of the
        same corpus."""
        from .columnar import ColumnarBuilder
        builder = ColumnarBuilder(scheme=self.scheme, method=self.method)
        for lv in (self._levels() if levels is None else levels):
            if lv.is_frozen:
                builder.absorb_index(lv)
            else:
                builder.absorb_builder(lv)
        return builder

    def freeze(self) -> SearchIndex:
        """Merge frozen + deltas into one in-memory ``SearchIndex`` (the
        build→serve handoff; use :meth:`compact` to persist in place)."""
        return self._merged_builder().freeze(arena=True)

    # Overlapped (two-phase) compaction: the server's engine thread calls
    # ``seal_delta`` (cheap pointer swap), a background thread runs
    # ``merge_sealed`` over the now-immutable frozen + sealed levels while
    # queries and adds keep flowing, and the engine thread finishes with
    # ``promote_sealed`` between batches.  Local ids never move (sealed
    # texts keep their offsets inside the new frozen), so queries started
    # before, during, or after any phase see identical results.

    @engine_only
    def seal_delta(self) -> int:
        """Phase 1: freeze the active delta as the ``sealed`` level and
        start a fresh one; returns the number of texts sealed.  Must not
        overlap a previous unfinished seal."""
        if self.sealed is not None:
            raise RuntimeError("a sealed delta is already being compacted")
        if len(self.doc_map) != self.num_texts:
            raise RuntimeError(
                f"doc_map has {len(self.doc_map)} entries for "
                f"{self.num_texts} texts; refusing to seal a torn state")
        self.sealed = self.delta
        self.delta = IndexBuilder(scheme=self.scheme, method=self.method)
        self._delta_born = None
        # snapshot the doc ids the merged generation will cover; adds keep
        # appending to doc_map but never touch this prefix
        self._sealed_docs = list(self.doc_map[:self.frozen.num_texts +
                                              self.sealed.num_texts])
        # every sealed doc's WAL record has an LSN below next_lsn (appends
        # precede indexing), so this is the watermark the merged
        # generation's manifest will carry
        if self.wal is not None:
            self._sealed_watermark = self.wal.next_lsn
        return self.sealed.num_texts

    @engine_only
    def unseal_delta(self) -> bool:
        """Roll back an unfinished overlapped compaction: restore the
        sealed level as the active delta, as if ``seal_delta`` never ran.

        Only possible while the active delta is still empty (no add
        landed since the seal).  Otherwise the sealed level stays — it is
        still served correctly as a middle level — and returns ``False``
        so the caller retries ``merge_sealed`` later instead.
        """
        if self.sealed is None:
            return False
        if self.delta.num_texts:
            return False
        self.delta = self.sealed
        self.sealed = None
        self._sealed_docs = []
        # rollback keeps every WAL segment: the un-promoted records are
        # live again and must replay after a crash
        self._sealed_watermark = None
        self._delta_born = (time.monotonic() if self.delta.num_texts
                            else None)
        return True

    @engine_only(reads_immutable=True)
    def merge_sealed(self) -> tuple[int, SearchIndex]:
        """Phase 2: fold frozen + sealed into a NEW committed (manifest on
        disk, ``CURRENT`` untouched) store generation.  Reads only
        immutable state, so it can run off-thread under live traffic.
        Returns ``(generation, its SearchIndex)`` for ``promote_sealed``."""
        if self.sealed is None:
            raise RuntimeError("nothing sealed: call seal_delta() first")
        if self.root is None:
            raise RuntimeError(
                "this LiveIndex is not store-backed; compaction writes a "
                "new store generation — open it with LiveIndex.open(path) "
                "(or use freeze() for an in-memory merge)")
        gen = index_store.next_generation(self.root)
        gen_dir = index_store.generation_dir(self.root, gen)
        new_idx = self._merged_builder(
            levels=(self.frozen, self.sealed)).freeze_to_store(
            gen_dir, mmap=self.mmap, include_scheme=self.scheme_in_manifest,
            doc_map=self._sealed_docs, wal_watermark=self._sealed_watermark)
        return gen, new_idx

    @engine_only
    def promote_sealed(self, gen: int, new_idx: SearchIndex) -> int:
        """Phase 3: flip the store's ``CURRENT`` pointer to ``gen`` and
        swap serving onto its index, retiring the sealed level.  Atomic
        from a query's point of view: local ids are unchanged, and
        in-flight queries holding the old (frozen, sealed, delta) refs
        finish against them bit-identically."""
        if self.sealed is None:
            raise RuntimeError("nothing sealed: call seal_delta() first")
        index_store.promote_generation(self.root, gen)
        self.frozen = new_idx
        self.sealed = None
        self._sealed_docs = []
        self.generation = gen
        if self.wal is not None and self._sealed_watermark is not None:
            # the promoted manifest covers everything below the watermark:
            # drop the covered segments and the dedup entries whose docs
            # now live in the frozen generation (the retry window is the
            # un-compacted suffix, by contract)
            self._wal_covered = self._sealed_watermark
            self.wal.truncate_upto(self._sealed_watermark)
            self._requests = {rid: lid for rid, lid in self._requests.items()
                              if lid >= new_idx.num_texts}
        self._sealed_watermark = None
        return gen

    @engine_only
    def compact(self, *, promote: bool = True) -> int:
        """Fold the delta into a NEW store generation and promote it.

        Streams the merged columns through ``IndexWriter`` into
        ``v{N:06d}/`` (arrays first, manifest last), then atomically flips
        the ``CURRENT`` pointer and swaps serving onto the mmap'd new
        generation with a fresh empty delta.  The old generation is
        retained for rollback; an interrupted compaction leaves the
        serving generation untouched (no manifest → never promoted) and
        this index still serving frozen + delta.  ``promote=False`` stops
        after the manifest commit and returns the generation number — the
        sharded process fan-out promotes from the parent.

        This is the synchronous form of the seal → merge → promote
        overlapped sequence above (all three phases inline).
        """
        if self.root is None:
            raise RuntimeError(
                "this LiveIndex is not store-backed; compaction writes a "
                "new store generation — open it with LiveIndex.open(path) "
                "(or use freeze() for an in-memory merge)")
        if self.sealed is None and self.delta.num_texts == 0:
            # nothing to fold in: don't rewrite the whole corpus into a
            # duplicate generation (timer-driven compactors hit this)
            return self.generation
        if self.sealed is None:
            self.seal_delta()
            try:
                gen, new_idx = self.merge_sealed()
            except BaseException:
                # synchronous path: no add can have landed between seal and
                # merge, so un-seal and restore the pre-call state (a crash
                # mid-merge must leave the index exactly as it was)
                self.unseal_delta()
                raise
        else:
            gen, new_idx = self.merge_sealed()
        if promote:
            self.promote_sealed(gen, new_idx)
        return gen


def _shard_compact_payload(spec: dict, root: str, delta_state: dict,
                           doc_map: list[int]) -> int:
    """Process-pool worker: compact one shard's store, WITHOUT promoting.

    The delta travels as its pickled ``state_dict`` (dict tables of plain
    tuples); the scheme as its JSON spec (weight closures don't pickle).
    The worker commits the new generation's manifest and returns its
    number — the parent flips each shard's pointer and mmap-reloads, so a
    mid-fan-out crash leaves every shard serving its old generation.
    """
    from .schemes import scheme_from_spec
    live = LiveIndex.open(root, mmap=False, scheme=scheme_from_spec(spec))
    live.delta.load_state_dict(delta_state)
    live.doc_map = [int(g) for g in doc_map]
    return live.compact(promote=False)
