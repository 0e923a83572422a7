"""Distributed (multi-host) index build & query fan-out.

The corpus is sharded across data-parallel workers; each worker builds an
independent :class:`~repro.core.builder.IndexBuilder` over its shard (the
skyline partitioner is host-side; device kernels produce sketches --
DESIGN.md §2.2), or — on the batch path — a columnar
:class:`~repro.core.columnar.ColumnarBuilder` per shard, optionally in a
process pool with finished shards streamed straight into store
directories (``build(pipeline="columnar", fanout=..., store=...)``).
Queries broadcast the k sketch coordinates (O(k) bytes)
and union per-shard results.  Each shard checkpoints independently: a lost
worker rebuilds only its shard (fault tolerance), and shards can be
re-split when the worker count changes (elasticity).

Persistence is two-format by lifecycle stage:

* **frozen** shards (post ``freeze()``, :class:`SearchIndex`) are saved as
  versioned ``shard_{s}/`` store directories (:mod:`repro.core.store`) —
  JSON manifest + raw ``.npy`` arrays, restorable with ``mmap=True`` so a
  larger-than-RAM corpus serves without materializing the tables.
* **mutable** shards (mid-build ``IndexBuilder``) are pickled as
  ``shard_{s}.pkl`` build-time checkpoints, as before.

Live serving (``restore(..., live=True)``) wraps every store-backed shard
in a :class:`~repro.core.live.LiveIndex` — frozen mmap arrays plus a
small per-shard mutable delta — so the restored index takes ``add_text``
writes while serving, and :meth:`ShardedAlignmentIndex.compact` folds all
the deltas into new per-shard store generations (optionally fanned out
across a spawn process pool) with atomic per-shard promotion.
"""

from __future__ import annotations

import json
import pickle
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from ..fault import checkpoint as fault_checkpoint
from ..fault import fsio
from . import store as index_store
from .builder import IndexBuilder
from .query import Alignment, query, run_batch
from .results import UNSET, QueryOptions, coerce_query_options
from .search import SearchIndex

META_VERSION = 1


def shard_of(doc_id: int, n_shards: int) -> int:
    return doc_id % n_shards


@dataclass
class ShardedAlignmentIndex:
    """n_shards independent indexes with a global doc-id space."""

    scheme: object
    n_shards: int = 4
    method: str = "mono_active"
    shards: list = field(init=False)
    doc_map: list[tuple[int, int]] = field(default_factory=list)
    # doc_map[global_id] = (shard, local_id)
    _inverse: dict | None = field(default=None, init=False, repr=False)
    _pool: object = field(default=None, init=False, repr=False)
    _root: Path | None = field(default=None, init=False, repr=False)

    def __post_init__(self):
        self.shards = [IndexBuilder(scheme=self.scheme, method=self.method)
                       for _ in range(self.n_shards)]

    def add_text(self, tokens) -> int:
        gid = len(self.doc_map)
        s = shard_of(gid, self.n_shards)
        shard = self.shards[s]
        if getattr(shard, "is_live", False):
            # live shard: the delta takes the write; pin the global id so
            # the shard's own doc_map (persisted at compaction) stays in
            # step with ours
            lid = shard.add_text(np.asarray(tokens, np.int64), gid=gid)
        elif shard.is_frozen:
            raise RuntimeError(
                f"shard {s} is frozen (SearchIndex); adds belong to the "
                "build stage — restore(live=True) for incremental serving, "
                "or rebuild the shard with an IndexBuilder")
        else:
            lid = shard.add_text(np.asarray(tokens, np.int64))
        self.doc_map.append((s, lid))
        self._inverse = None              # invalidate the cached inverse map
        return gid

    def build(self, texts, *, pipeline: str = "dict",
              fanout: str = "serial", store: str | Path | None = None,
              mmap: bool = True) -> "ShardedAlignmentIndex":
        """Index a corpus across the shards.

        ``pipeline="dict"`` (default) is the incremental path: every text
        goes through ``add_text`` into its shard's mutable dict builder.

        ``pipeline="columnar"`` is the batch path: documents are
        partitioned across shards up front and each shard is built by a
        :class:`~repro.core.columnar.ColumnarBuilder` and frozen — the
        shards come out as serving-ready ``SearchIndex`` objects
        (block-identical to dict-build + ``freeze()``).  ``fanout`` picks
        the shard-level parallelism:

        * ``"serial"``   — one shard after another, in-process.
        * ``"threaded"`` — a thread pool; the vectorized sort/pack stages
          release the GIL, the Python partition loop does not, so gains
          are workload-dependent.
        * ``"process"``  — a spawn-based process pool; the columnar build
          is no longer dict-mutation-bound, so shards scale across cores.
          The scheme travels as its JSON ``scheme_spec``.

        ``store=`` streams every finished shard straight into
        ``store/shard_{s}`` store directories (plus the root ``meta.json``)
        and restores the shards from there (``mmap=True`` maps them) —
        corpus to saved sharded store in one pass, without ever holding
        all shards' tables in RAM.  With ``fanout="process"`` the shard
        arrays then never cross the process boundary at all.
        """
        if pipeline == "dict":
            if fanout != "serial" or store is not None:
                raise ValueError(
                    "fanout/store are columnar-pipeline options; the dict "
                    'pipeline is incremental — use pipeline="columnar"')
            for t in texts:
                self.add_text(t)
            return self
        if pipeline != "columnar":
            raise ValueError(f"unknown pipeline {pipeline!r}; "
                             "expected 'dict' or 'columnar'")
        if fanout not in ("serial", "threaded", "process"):
            # validate BEFORE touching doc_map / store dirs: a failed call
            # must leave the index untouched and retryable
            raise ValueError(f"unknown fanout {fanout!r}; expected "
                             "'serial', 'threaded' or 'process'")
        if self.doc_map:
            raise RuntimeError(
                "columnar build requires an empty index (it assigns the "
                "whole corpus to shards up front); use add_text / the dict "
                "pipeline to grow an existing one")
        docs = [np.asarray(t, np.int64) for t in texts]
        per_shard: list[list] = [[] for _ in range(self.n_shards)]
        for gid, d in enumerate(docs):
            s = shard_of(gid, self.n_shards)
            self.doc_map.append((s, len(per_shard[s])))
            per_shard[s].append(d)
        self._inverse = None
        root = None
        if store is not None:
            root = Path(store)
            root.mkdir(parents=True, exist_ok=True)
            self._root = root
        dirs = [root / f"shard_{s}" if root is not None else None
                for s in range(self.n_shards)]
        if fanout == "process":
            self._build_shards_process(per_shard, dirs, mmap)
        else:
            from .columnar import ColumnarBuilder

            def build_one(s: int):
                builder = ColumnarBuilder(
                    scheme=self.scheme,
                    method=self.method).build(per_shard[s])
                if dirs[s] is not None:
                    return builder.freeze_to_store(
                        dirs[s], mmap=mmap, include_scheme=False,
                        doc_map=self.docs_of_shard(s))
                return builder.freeze()

            if fanout == "threaded" and self.n_shards > 1:
                shards = list(self._fanout_pool().map(
                    build_one, range(self.n_shards)))
            else:
                shards = [build_one(s) for s in range(self.n_shards)]
            self.shards = shards
        if root is not None:
            self._write_meta(root)
        return self

    def _build_shards_process(self, per_shard, dirs, mmap: bool) -> None:
        """Columnar-build every shard in a spawn process pool."""
        import os
        from concurrent.futures import ProcessPoolExecutor
        from multiprocessing import get_context

        from .columnar import _shard_build_payload
        from .schemes import scheme_spec
        spec = scheme_spec(self.scheme)      # workers rebuild the scheme
        workers = min(self.n_shards, os.cpu_count() or 1)
        with ProcessPoolExecutor(max_workers=workers,
                                 mp_context=get_context("spawn")) as pool:
            futures = [
                pool.submit(_shard_build_payload, spec, self.method,
                            per_shard[s],
                            str(dirs[s]) if dirs[s] is not None else None,
                            self.docs_of_shard(s))
                for s in range(self.n_shards)]
            for s, fut in enumerate(futures):
                payload = fut.result()
                if dirs[s] is not None:
                    # just written by the worker: skip checksum verification
                    self.shards[s] = index_store.load_index(
                        dirs[s], mmap=mmap, scheme=self.scheme, verify=False)
                else:
                    self.shards[s] = SearchIndex.from_state(
                        self.scheme, payload)

    def query(self, tokens, theta: float) -> list[Alignment]:
        """Fan-out / union; local ids remapped into the global space."""
        out: list[Alignment] = []
        inverse = self._inverse_doc_map()
        for s, shard in enumerate(self.shards):
            for al in query(shard, tokens, theta):
                out.append(Alignment(text_id=inverse[(s, al.text_id)],
                                     blocks=al.blocks, ncoords=al.ncoords))
        return sorted(out, key=lambda a: a.text_id)

    def batch_query(self, texts, theta: float, *,
                    options: QueryOptions | None = None,
                    sketches=UNSET, backend=UNSET, fanout=UNSET,
                    stage_times: dict | None = None,
                    failures: list | None = None,
                    shard_retries: int = 1,
                    retry_backoff_s: float = 0.005) -> list[list[Alignment]]:
        """Batched fan-out: the batch pipeline
        (:func:`repro.core.query.run_batch`) sketches the batch once
        (shards share the hash family), probes every shard's levels with
        the same sketches, sweeps them, and unions per query in the global
        id space.

        Execution comes in as ``options=QueryOptions(...)`` whose ``plan``
        is resolved once for the whole fan-out (every shard runs the same
        resolved stages; ``plan="device"`` probes each frozen shard's
        resident arena).  The pre-redesign ``sketches``/``backend``/
        ``fanout`` keywords still work behind a ``DeprecationWarning``.

        ``QueryOptions.fanout="threaded"`` (default) overlaps the
        per-shard *probe* stage with a thread pool — NumPy releases the
        GIL inside searchsorted/gather and mmap-backed shards overlap
        page-ins — and then runs the GIL-bound plane-sweep stage serially
        (threading it just convoys on the GIL); ``"serial"`` keeps the
        fully sequential loop.  Results are merged in shard order either
        way, so the two are block-identical.  ``stage_times`` accumulates
        per-stage wall seconds under ``"sketch"``/``"probe"``/``"sweep"``
        and their children (:mod:`repro.core.spans`) when given; the pool
        threads of the probe fan-out write into no shared dict.

        **Degraded mode**: with ``failures`` set to a caller-owned list,
        a shard whose probe keeps raising after ``shard_retries`` bounded
        exponential-backoff retries is *skipped* — its shard id is
        appended to ``failures`` and the union simply misses its docs —
        instead of failing the whole fan-out.  With ``failures=None``
        (default) the first shard exception propagates, preserving the
        strict all-or-nothing semantics oracles rely on.
        """
        opts = coerce_query_options(
            options, "ShardedAlignmentIndex.batch_query", sketches=sketches,
            backend=backend, fanout=fanout)
        inverse = self._inverse_doc_map()

        def probe_shard(probe, s: int, shard, times: dict | None) -> list:
            attempts = 1 + (shard_retries if failures is not None else 0)
            delay = retry_backoff_s
            for attempt in range(attempts):
                try:
                    fault_checkpoint(f"sharded.probe.s{s}")
                    return probe(shard, times=times,
                                 ids=lambda t: inverse[(s, t)])
                except Exception:
                    if attempt + 1 >= attempts:
                        if failures is None:
                            raise
                        failures.append(s)
                        return []           # the union misses its docs
                    time.sleep(delay)
                    delay *= 2

        def fan_out(probe, how: str) -> list:
            if how == "threaded" and self.n_shards > 1:
                parts = self._fanout_pool().map(
                    lambda s: probe_shard(probe, s, self.shards[s], None),
                    range(self.n_shards))
            else:
                parts = [probe_shard(probe, s, shard, stage_times)
                         for s, shard in enumerate(self.shards)]
            return [level for part in parts for level in part]

        return run_batch(self, texts, theta, opts, stage_times,
                         fan_out=fan_out)

    def freeze(self) -> "ShardedAlignmentIndex":
        """Freeze every shard into the CSR serving layout (idempotent).
        Live shards merge their delta in memory (their store generations
        are untouched; use :meth:`compact` to persist in place)."""
        self.shards = [shard.freeze() for shard in self.shards]
        return self

    def compact(self, *, fanout: str = "serial") -> "ShardedAlignmentIndex":
        """Fold every live shard's delta into a new store generation and
        promote it (see :meth:`repro.core.live.LiveIndex.compact`).

        ``fanout="process"`` runs the per-shard merge-compactions in a
        spawn process pool — deltas travel as pickled state dicts, arrays
        never cross the boundary (workers write the generation dirs, the
        parent mmap-reloads) — and promotion always happens in the
        parent, one atomic pointer flip per shard, after that shard's
        manifest is committed.  The root ``meta.json`` is rewritten last
        with the grown doc map; per-shard manifests keep ``restore``
        correct even if a crash lands between the flips and that rewrite.
        """
        from .live import LiveIndex, _shard_compact_payload
        if fanout not in ("serial", "process"):
            raise ValueError(f"unknown fanout {fanout!r}; expected "
                             "'serial' or 'process'")
        live = [s for s in range(self.n_shards)
                if getattr(self.shards[s], "is_live", False)]
        if not live:
            raise RuntimeError(
                "no live shards to compact; restore the index with "
                "live=True (Aligner.load(path, live=True)) to serve writes")
        # shards whose delta levels are empty have nothing to fold in —
        # don't rewrite them into duplicate generations
        live = [s for s in live if self.shards[s].delta.num_texts
                or self.shards[s].sealed is not None]
        if not live:
            return self
        if fanout == "process" and len(live) > 1:
            import os
            from concurrent.futures import ProcessPoolExecutor
            from multiprocessing import get_context

            from .schemes import scheme_spec
            spec = scheme_spec(self.scheme)
            workers = min(len(live), os.cpu_count() or 1)
            with ProcessPoolExecutor(max_workers=workers,
                                     mp_context=get_context("spawn")) as pool:
                futures = {
                    s: pool.submit(_shard_compact_payload, spec,
                                   str(self.shards[s].root),
                                   self.shards[s].delta.state_dict(),
                                   self.shards[s].doc_map)
                    for s in live}
                gens = {s: fut.result() for s, fut in futures.items()}
            for s in live:
                shard = self.shards[s]
                index_store.promote_generation(shard.root, gens[s])
                self.shards[s] = LiveIndex.open(shard.root, mmap=shard.mmap,
                                                scheme=self.scheme)
        else:
            for s in live:
                self.shards[s].compact()
        if self._root is not None:
            self._write_meta(self._root)
        return self

    @property
    def is_frozen(self) -> bool:
        return all(s.is_frozen for s in self.shards)

    def nbytes(self) -> int:
        return sum(s.nbytes() for s in self.shards)

    def _fanout_pool(self):
        """Reused fan-out thread pool (spawning one per batch_query would
        pay n_shards thread start/joins on every serving call).  Lifetime
        is tied to the index: when it is dropped, CPython's executor
        weakref callback wakes the idle workers and they exit — no
        explicit shutdown needed."""
        if self._pool is None:
            import os
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=min(self.n_shards, os.cpu_count() or 1),
                thread_name_prefix="shard-fanout")
        return self._pool

    def _inverse_doc_map(self) -> dict[tuple[int, int], int]:
        """(shard, local_id) -> global_id, cached between queries (rebuilt
        lazily after ``add_text``/``restore`` invalidate it)."""
        if self._inverse is None or len(self._inverse) != len(self.doc_map):
            self._inverse = {(s, lid): gid
                             for gid, (s, lid) in enumerate(self.doc_map)}
        return self._inverse

    @property
    def num_windows(self) -> int:
        return sum(s.num_windows for s in self.shards)

    # -- per-shard persistence (fault tolerance / elasticity) ---------------

    def _write_meta(self, root: Path) -> None:
        from .schemes import scheme_spec
        meta = {"meta_version": META_VERSION, "n_shards": self.n_shards,
                "method": self.method, "doc_map": self.doc_map,
                "scheme": scheme_spec(self.scheme)}
        fsio.commit_text(root / "meta.json", json.dumps(meta),
                         site="sharded.meta")

    def save(self, root: str | Path):
        root = Path(root)
        root.mkdir(parents=True, exist_ok=True)
        if self._root is None:
            self._root = root          # snapshot saves don't retarget compact
        for s, shard in enumerate(self.shards):
            store_dir = root / f"shard_{s}"
            pkl = root / f"shard_{s}.pkl"
            if getattr(shard, "is_live", False):
                # snapshot a live shard as one flat merged store at the
                # target (its own store generations are untouched)
                shard = shard.freeze()
            if shard.is_frozen:
                # scheme spec lives once in meta.json (a tfidf spec carries
                # the corpus-wide doc-frequency table; don't write n copies)
                index_store.save_index(shard, store_dir,
                                       doc_map=self.docs_of_shard(s),
                                       include_scheme=False)
                # the snapshot is the flat layout; retire any generation
                # pointer AFTER its manifest commit so readers flip from a
                # complete old generation to the complete new snapshot
                fsio.unlink(store_dir / index_store.CURRENT_POINTER,
                            site="sharded.retire_pointer", missing_ok=True)
                fsio.unlink(pkl, site="sharded.retire_checkpoint",
                            missing_ok=True)      # drop stale checkpoint
            else:
                # atomic commit (tmp + rename inside commit_bytes)
                fsio.commit_bytes(pkl, pickle.dumps(shard.state_dict()),
                                  site="sharded.checkpoint")
                if store_dir.exists():
                    fsio.rmtree(store_dir,
                                site="sharded.reset")  # drop stale store
        self._write_meta(root)

    def restore(self, root: str | Path, *, missing_ok: bool = True,
                mmap: bool = False, live: bool = False) -> list[int]:
        """Load shards from disk; returns the list of shard ids that were
        missing/corrupt and have been rebuilt empty (the caller re-adds only
        those shards' documents -- partial recovery).

        ``mmap=True`` maps frozen shards' table arrays instead of reading
        them into RAM (versioned store directories only; pickled build
        checkpoints always materialize).  ``live=True`` wraps every
        store-backed shard in a :class:`~repro.core.live.LiveIndex` so the
        restored index accepts ``add_text`` and ``compact()`` without
        thawing (mutable pickled shards already accept adds and load as
        usual).

        The global id mapping is taken from the per-shard store manifests
        where available (they are rewritten on every compaction promote),
        with ``meta.json`` covering mutable/lost shards — so a shard
        compacted after the root meta was last written still restores with
        correct global ids.
        """
        root = Path(root)
        meta = json.loads((root / "meta.json").read_text())
        if meta["n_shards"] != self.n_shards:
            raise ValueError(
                f"shard-count mismatch: checkpoint at {root} has "
                f"{meta['n_shards']} shards but this index was built with "
                f"n_shards={self.n_shards}; construct the index with the "
                "checkpoint's shard count, or re-shard the corpus and "
                "rebuild (elastic re-shard)")
        self.doc_map = [tuple(x) for x in meta["doc_map"]]
        self._inverse = None
        self._root = root
        lost = []
        for s in range(self.n_shards):
            try:
                self.shards[s] = self._load_shard(root, s, mmap=mmap,
                                                  live=live)
            except Exception:
                if not missing_ok:
                    raise
                self.shards[s] = IndexBuilder(scheme=self.scheme,
                                              method=self.method)
                lost.append(s)
        self._remap_doc_ids_from_stores(root, lost)
        return lost

    def _remap_doc_ids_from_stores(self, root: Path, lost: list[int]) -> None:
        """Overlay the per-shard store manifests' ``doc_map`` onto the
        global map: local id ``lid`` of shard ``s`` serves global doc
        ``manifest.doc_map[lid]``.  The manifests are authoritative for
        frozen shards (promotion rewrites them atomically with the
        arrays); ``meta.json`` keeps covering pickled shards and lost
        shards' documents, and contiguous shard-local ids are no longer
        assumed anywhere."""
        for s in range(self.n_shards):
            store_dir = root / f"shard_{s}"
            if s in lost or not index_store.is_index_store(store_dir):
                continue
            shard_map = index_store.read_manifest(store_dir).get("doc_map")
            if shard_map is None:
                continue
            for lid, gid in enumerate(shard_map):
                gid = int(gid)
                if gid >= len(self.doc_map):
                    self.doc_map.extend(
                        [None] * (gid + 1 - len(self.doc_map)))
                self.doc_map[gid] = (s, lid)
        holes = [g for g, e in enumerate(self.doc_map) if e is None]
        if holes:
            raise ValueError(
                f"global doc ids {holes[:8]}{'...' if len(holes) > 8 else ''}"
                f" appear in no shard manifest and predate {root}/meta.json;"
                " the store is torn — re-save the index or restore the "
                "missing shard stores")
        self._inverse = None

    def _load_shard(self, root: Path, s: int, *, mmap: bool,
                    live: bool = False):
        store_dir = root / f"shard_{s}"
        if index_store.is_index_store(store_dir):
            if live:
                from .live import LiveIndex
                return LiveIndex.open(store_dir, mmap=mmap,
                                      scheme=self.scheme)
            return index_store.load_index(store_dir, mmap=mmap,
                                          scheme=self.scheme)
        with open(root / f"shard_{s}.pkl", "rb") as f:
            state = pickle.load(f)
        if state.get("frozen") is not None:
            return SearchIndex.from_state(self.scheme, state)
        builder = IndexBuilder(scheme=self.scheme, method=self.method)
        builder.load_state_dict(state)
        return builder

    def docs_of_shard(self, s: int) -> list[int]:
        return [gid for gid, (sh, _l) in enumerate(self.doc_map) if sh == s]
