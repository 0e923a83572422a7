"""`repro.api` — the one-object service facade over the paper's pipeline.

The workload is index-once/query-many: build k inverted indexes of compact
windows over a corpus, then serve threshold-θ alignment queries.  The
:class:`Aligner` makes that lifecycle explicit::

    from repro.api import Aligner

    aligner = Aligner.build(corpus, similarity="tfidf", k=32)   # build
    hits = aligner.find(query, theta=0.8)                       # query
    aligner.save("idx_dir")                                     # freeze+persist

    server = Aligner.load("idx_dir", mmap=True)                 # serve (mmap)
    results = server.find_batch(queries, theta=0.8)

    live = Aligner.load("idx_dir", live=True)                   # live serve
    live.add(new_doc)                  # served immediately (delta index)
    live.compact()                     # fold into a new store generation

``build`` fits the weight function from the corpus (``WeightFn.fit``),
constructs the sketch scheme through the :func:`repro.core.make_scheme`
registry, and indexes every document — sharded across
:class:`~repro.core.sharded_index.ShardedAlignmentIndex` when
``shards > 1``.  ``save`` freezes the dict build tables into immutable CSR
``SearchIndex`` arrays and writes the versioned directory store;
``load(mmap=True)`` maps those arrays back with ``np.load(mmap_mode="r")``
so a larger-than-RAM corpus serves queries through the OS page cache.

Documents and queries may be token arrays or plain strings — strings are
encoded with the (deterministic, stateless) tokenizer, which round-trips
through the store manifest.
"""

from __future__ import annotations

import dataclasses
import json
import warnings
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .fault import fsio

from .core import batch_query as _batch_query, make_scheme
from .core.builder import IndexBuilder
from .core.live import LiveIndex
from .core.query import Alignment
from .core.results import (UNSET, Match, QueryOptions, QueryResult,
                           coerce_query_options)
from .core.search import SearchIndex
from .core.sharded_index import ShardedAlignmentIndex
from .core.spans import span
from .core.store import (CURRENT_POINTER, load_index, read_manifest,
                         save_index)
from .core.weights import WeightFn

_ALIGNER_META = "aligner.json"


@dataclass(frozen=True)
class AlignerConfig:
    """Everything ``Aligner.build`` needs besides the corpus.

    similarity: "tfidf" (corpus-fitted TF-IDF weighted Jaccard, the
        default), "weighted" (TF-only weighted Jaccard, corpus-free), or
        "multiset" (unweighted multi-set Jaccard).
    k: sketch width (number of hash functions / inverted tables).
    shards: >1 builds a sharded index (per-shard checkpoints, fan-out).
    method: compact-window partitioner ("mono_active", "mono_all",
        "allalign").
    tf / idf: weight-function kinds (Table 1); ``idf=None`` picks the
        similarity's default ("smooth" for tfidf, "unary" for weighted).
    family: multiset hash family ("universal" or "mix").
    """

    similarity: str = "tfidf"
    k: int = 16
    shards: int = 1
    method: str = "mono_active"
    seed: int = 0
    tf: str = "raw"
    idf: str | None = None
    family: str = "universal"

    def make_scheme(self, corpus=None):
        idf = self.idf or {"tfidf": "smooth"}.get(self.similarity, "unary")
        return make_scheme(self.similarity, seed=self.seed, k=self.k,
                           tf=self.tf, idf=idf, family=self.family,
                           corpus=corpus)


class Aligner:
    """Build→serve facade: index a corpus once, serve alignment queries.

    Construct via :meth:`build` (fresh index) or :meth:`load` (saved
    store); the raw constructor wires pre-built parts together and is
    mostly internal.
    """

    def __init__(self, index, *, config: AlignerConfig | None = None,
                 tokenizer=None):
        self._index = index
        self.config = config or AlignerConfig()
        self.tokenizer = tokenizer

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, corpus, *, similarity: str = "tfidf", k: int = 16,
              shards: int = 1, method: str = "mono_active", seed: int = 0,
              tf: str = "raw", idf: str | None = None,
              family: str = "universal", tokenizer=None,
              pipeline: str = "dict", fanout: str = "serial",
              store=None, mmap: bool = True,
              config: AlignerConfig | None = None) -> "Aligner":
        """Fit weights from ``corpus``, construct the scheme, and index
        every document.  ``corpus`` is an iterable of token arrays or
        strings (strings are tokenized; pass ``tokenizer=`` to control
        how, else a default ``HashWordTokenizer`` is used).

        ``pipeline`` picks the construction path: ``"dict"`` (default)
        builds mutable dict tables that stay open for :meth:`add`;
        ``"columnar"`` runs the batch columnar pipeline — the index comes
        back already frozen (block-identical tables, several times faster
        to build).  With ``pipeline="columnar"``: ``fanout``
        ("serial"/"threaded"/"process") parallelizes a sharded build
        across shards, and ``store=`` streams the finished index straight
        into a versioned store directory (``mmap=True`` serves from the
        mapped arrays) — corpus to saved, serving-ready store in one
        pass, no separate :meth:`save` needed."""
        if config is None:
            config = AlignerConfig(similarity=similarity, k=k, shards=shards,
                                   method=method, seed=seed, tf=tf, idf=idf,
                                   family=family)
        if pipeline not in ("dict", "columnar"):
            raise ValueError(f"unknown pipeline {pipeline!r}; "
                             "expected 'dict' or 'columnar'")
        if fanout not in ("serial", "threaded", "process"):
            raise ValueError(f"unknown fanout {fanout!r}; expected "
                             "'serial', 'threaded' or 'process'")
        if pipeline == "dict" and (store is not None or fanout != "serial"):
            raise ValueError(
                "store/fanout are columnar-pipeline options; pass "
                'pipeline="columnar"')
        docs = list(corpus)
        if docs and isinstance(docs[0], str) and tokenizer is None:
            from .data.tokenizer import HashWordTokenizer
            tokenizer = HashWordTokenizer()
        self = cls(None, config=config, tokenizer=tokenizer)
        token_docs = [self._tokens(d) for d in docs]
        scheme = config.make_scheme(corpus=token_docs)
        if config.shards > 1:
            self._index = ShardedAlignmentIndex(
                scheme=scheme, n_shards=config.shards, method=config.method)
            self._index.build(token_docs, pipeline=pipeline, fanout=fanout,
                              store=store, mmap=mmap)
        elif pipeline == "columnar":
            from .core.columnar import ColumnarBuilder
            builder = ColumnarBuilder(
                scheme=scheme, method=config.method).build(token_docs)
            if store is not None:
                self._index = builder.freeze_to_store(store, mmap=mmap)
            else:
                self._index = builder.freeze(arena=True)
        else:
            self._index = IndexBuilder(
                scheme=scheme, method=config.method).build(token_docs)
        if store is not None:
            self._write_meta(Path(store))
        return self

    # -- lifecycle ----------------------------------------------------------

    @property
    def is_frozen(self) -> bool:
        return self._index.is_frozen

    def add(self, text, *, request_id: str | None = None) -> int:
        """Index one more document; returns its (global) doc id.

        Valid in the build stage and on a live-loaded Aligner
        (``Aligner.load(path, live=True)``), where the write lands in the
        mutable delta and is served immediately alongside the frozen
        store.

        ``request_id`` (live indexes only) makes the call idempotent
        within the un-compacted window: a replayed id returns the
        original doc id without indexing a duplicate.  With a WAL open
        (``Aligner.load(..., wal=...)``) the id is logged into the WAL
        record so the window survives crash replay."""
        if isinstance(self._index, LiveIndex):
            lid = self._index.add_text(self._tokens(text),
                                       request_id=request_id)
            return self._index.doc_map[lid]
        if self.is_frozen:
            raise RuntimeError(
                "this Aligner serves a frozen index; reload it with "
                "Aligner.load(path, live=True) to accept writes, or build "
                "a new index (Aligner.build) to grow the corpus")
        return self._index.add_text(self._tokens(text))

    def freeze(self) -> "Aligner":
        """Finalize the build: compact every table into the immutable CSR
        serving layout (idempotent).  A live index merges its delta in
        memory (the on-disk store is untouched; use :meth:`compact` to
        persist in place)."""
        self._index = self._index.freeze()
        return self

    def compact(self, *, fanout: str = "serial") -> "Aligner":
        """Fold a live Aligner's delta into a new store generation and
        atomically promote it (old generation retained for rollback).
        Sharded live indexes compact every shard — ``fanout="process"``
        spreads the per-shard merges across a spawn process pool."""
        if isinstance(self._index, LiveIndex):
            self._index.compact()
        elif isinstance(self._index, ShardedAlignmentIndex):
            self._index.compact(fanout=fanout)
        else:
            raise RuntimeError(
                "compact() folds a live delta into its store; load the "
                "index with Aligner.load(path, live=True) first")
        return self

    # -- queries ------------------------------------------------------------

    def find(self, text, theta: float, *,
             options: QueryOptions | None = None,
             legacy_tuples: bool = False,
             stage_times: dict | None = None) -> QueryResult:
        """All indexed subsequences aligned with ``text`` at estimated
        (weighted) Jaccard >= theta (paper Definition 1), as a
        :class:`~repro.core.results.QueryResult` of typed
        :class:`~repro.core.results.Match` records (iterating it yields
        the matches, so ``for hit in aligner.find(...)`` is unchanged).

        ``legacy_tuples=True`` returns the pre-typed ``list[Alignment]``
        shape behind a ``DeprecationWarning``."""
        # repro: allow[RPR402] (the shim forwards its own legacy flag)
        return self.find_batch([text], theta, options=options,
                               legacy_tuples=legacy_tuples,
                               stage_times=stage_times)[0]

    def find_batch(self, texts, theta: float, *,
                   options: QueryOptions | None = None,
                   backend=UNSET, sketch_backend=UNSET,
                   legacy_tuples: bool = False,
                   stage_times: dict | None = None) -> list[QueryResult]:
        """Batched :meth:`find` (the serving path — one fused arena probe
        for the whole batch); one :class:`QueryResult` per input text.

        Execution comes in as ``options=QueryOptions(...)``, whose
        ``plan`` names the pipeline: ``"cpu"`` (NumPy reference path, the
        default), ``"device"`` (the arena stays resident on the
        accelerator; probe and sweep run there, block-identical
        to cpu), or ``"auto"`` (device when a real accelerator backs jax,
        else silently cpu).  ``QueryOptions(sketch_backend="pallas")``
        moves weighted-scheme sketching into the fused device kernel.
        Sharded indexes fan the probes out across a thread pool
        (``QueryOptions.fanout``).

        The pre-redesign ``backend``/``sketch_backend`` keywords still
        work for one release behind a ``DeprecationWarning`` (they coerce
        to pins on the cpu plan), as
        does ``legacy_tuples=True`` for the old ``list[list[Alignment]]``
        return shape.  ``stage_times`` accumulates per-stage wall seconds
        under ``"sketch"``/``"probe"``/``"sweep"``, their children and
        ``"results"`` (the names of :mod:`repro.core.spans`; the
        serve-path metrics hook)."""
        opts = coerce_query_options(options, "Aligner.find_batch",
                                    backend=backend,
                                    sketch_backend=sketch_backend)
        tokens = [self._tokens(t) for t in texts]
        failed: list[int] = []
        if isinstance(self._index, ShardedAlignmentIndex):
            # degraded fan-out: a shard that keeps failing is skipped
            # (retried with backoff) and reported on the results instead
            # of failing the whole batch
            res = self._index.batch_query(tokens, theta, options=opts,
                                          stage_times=stage_times,
                                          failures=failed)
        elif isinstance(self._index, LiveIndex):
            res = self._index.batch_query(tokens, theta, options=opts,
                                          stage_times=stage_times)
        else:
            res = _batch_query(self._index, tokens, theta, options=opts,
                               stage_times=stage_times)
        if legacy_tuples:
            warnings.warn(
                "legacy_tuples=True is deprecated; Aligner.find/find_batch "
                "return typed QueryResult containers of Match records "
                "(iteration, len() and truthiness are unchanged)",
                DeprecationWarning, stacklevel=2)
            return res
        k = self.scheme.k
        with span(stage_times, "results"):
            results = [QueryResult.from_alignments(r, theta=theta, k=k,
                                                   query_len=len(t))
                       for r, t in zip(res, tokens)]
            if failed:
                fs = tuple(sorted(set(failed)))
                results = [dataclasses.replace(r, degraded=True,
                                               failed_shards=fs)
                           for r in results]
        return results

    # -- persistence --------------------------------------------------------

    def _write_meta(self, root: Path) -> None:
        meta = {"similarity": self.config.similarity,
                "tokenizer": _tokenizer_spec(self.tokenizer)}
        fsio.write_text(root / _ALIGNER_META, json.dumps(meta),
                        site="aligner.meta")

    def save(self, path) -> "Aligner":
        """Freeze (if still building) and write the versioned store: JSON
        manifests + raw ``.npy`` arrays per frozen table, one directory per
        index (per shard when sharded).

        A live Aligner snapshots frozen + delta as one flat merged store
        at ``path`` without disturbing its own serving state (its store
        generations persist via :meth:`compact`, not here).  Snapshotting
        over the store this Aligner is *serving from* is refused — that
        would rewrite the mmap'd arrays in place under the reader; use
        :meth:`compact` to persist the delta there."""
        root = Path(path)
        if isinstance(self._index, LiveIndex):
            live = self._index
            self._refuse_live_overwrite(root, [live.root])
            identity = live.doc_map == list(range(len(live.doc_map)))
            save_index(live.freeze(), root,
                       doc_map=None if identity else live.doc_map)
            # the snapshot is flat: retire any stale generation pointer at
            # the target AFTER the manifest commit, so readers flip from a
            # complete old generation to the complete snapshot
            fsio.unlink(root / CURRENT_POINTER,
                        site="aligner.retire_pointer", missing_ok=True)
            self._write_meta(root)
            return self
        if isinstance(self._index, ShardedAlignmentIndex):
            live_shards = [s for s in self._index.shards
                           if getattr(s, "is_live", False)]
            if live_shards:
                self._refuse_live_overwrite(
                    root, [s.root.parent for s in live_shards
                           if s.root is not None])
            else:
                self.freeze()
            # live shards are snapshot-merged inside save() without
            # disturbing this aligner's serving state
            self._index.save(root)
        else:
            self.freeze()
            save_index(self._index, root)
        self._write_meta(root)
        return self

    @staticmethod
    def _refuse_live_overwrite(root: Path, serving_roots) -> None:
        for served in serving_roots:
            if served is not None and root.resolve() == served.resolve():
                raise RuntimeError(
                    "refusing to snapshot a live Aligner over the store it "
                    f"is serving from ({root}): np.save would truncate the "
                    "mmap'd arrays under the reader; use compact() to "
                    "persist the delta there, or save to a new directory")

    @classmethod
    def load(cls, path, *, mmap: bool = True, live: bool = False,
             wal=False) -> "Aligner":
        """Load a saved store and serve from it.  ``mmap=True`` (default)
        maps the table arrays read-only instead of materializing them —
        the serving mode for larger-than-RAM indexes.

        ``live=True`` opens the store for *incremental* serving: the
        returned Aligner accepts :meth:`add` without thawing (writes land
        in a small mutable delta, queried alongside the frozen arrays)
        and :meth:`compact` folds the delta into a new, atomically
        promoted store generation.  Sharded stores get one delta per
        shard.

        ``wal`` (flat live stores only) opens a write-ahead log under
        the store dir: every :meth:`add` is logged before it is indexed,
        un-compacted writes are replayed on the next open, and
        :meth:`compact` truncates the covered log suffix.  Pass ``True``
        for the default per-record fsync policy or a
        :class:`repro.wal.WalConfig` to choose group-commit batching."""
        root = Path(path)
        meta = {}
        if (root / _ALIGNER_META).exists():
            meta = json.loads((root / _ALIGNER_META).read_text())
        if (root / "meta.json").exists():               # sharded layout
            if wal:
                raise ValueError(
                    "wal is supported for flat live stores only "
                    "(per-shard WALs are future work)")
            smeta = json.loads((root / "meta.json").read_text())
            from .core import scheme_from_spec
            manifest_scheme = smeta["scheme"]
            index = ShardedAlignmentIndex(
                scheme=scheme_from_spec(manifest_scheme),
                n_shards=smeta["n_shards"], method=smeta["method"])
            index.restore(root, missing_ok=False, mmap=mmap, live=live)
        else:                                           # flat layout
            if wal and not live:
                raise ValueError("wal requires live=True")
            index = (LiveIndex.open(root, mmap=mmap, wal=wal) if live
                     else load_index(root, mmap=mmap))
            manifest_scheme = read_manifest(root)["scheme"]
        weight = manifest_scheme.get("weight") or {}
        config = AlignerConfig(
            similarity=meta.get("similarity", manifest_scheme["kind"]),
            k=manifest_scheme["k"], seed=manifest_scheme["seed"],
            method=index.method,
            tf=weight.get("tf", "raw"), idf=weight.get("idf"),
            family=manifest_scheme.get("family", "universal"),
            shards=(index.n_shards
                    if isinstance(index, ShardedAlignmentIndex) else 1))
        return cls(index, config=config,
                   tokenizer=_tokenizer_from_spec(meta.get("tokenizer")))

    # -- introspection ------------------------------------------------------

    @property
    def scheme(self):
        return self._index.scheme

    @property
    def num_docs(self) -> int:
        if isinstance(self._index, (ShardedAlignmentIndex, LiveIndex)):
            return len(self._index.doc_map)
        return self._index.num_texts

    @property
    def num_windows(self) -> int:
        return self._index.num_windows

    def nbytes(self) -> int:
        return self._index.nbytes()

    def __repr__(self) -> str:
        live = isinstance(self._index, LiveIndex) or (
            isinstance(self._index, ShardedAlignmentIndex) and
            any(getattr(s, "is_live", False) for s in self._index.shards))
        stage = "live" if live else "serve" if self.is_frozen else "build"
        return (f"Aligner(similarity={self.config.similarity!r}, "
                f"k={self.config.k}, shards={self.config.shards}, "
                f"docs={self.num_docs}, windows={self.num_windows}, "
                f"stage={stage!r})")

    # -- helpers ------------------------------------------------------------

    def _tokens(self, text) -> np.ndarray:
        if isinstance(text, str):
            if self.tokenizer is None:
                # inventing a tokenizer here would encode the query with a
                # vocabulary the index was never built with (silent garbage)
                raise ValueError(
                    "this Aligner has no tokenizer (the corpus was token "
                    "arrays, or the build tokenizer did not round-trip "
                    "through the store); pass token arrays, or set "
                    ".tokenizer to the one used at build time")
            return np.asarray(self.tokenizer.encode(text), np.int64)
        return np.asarray(text, np.int64)


def _tokenizer_spec(tok) -> dict | None:
    from .data.tokenizer import ByteTokenizer, HashWordTokenizer
    if tok is None:
        return None
    if isinstance(tok, HashWordTokenizer):
        return {"kind": "hash_word", "vocab": tok.vocab,
                "lowercase": tok.lowercase}
    if isinstance(tok, ByteTokenizer):
        return {"kind": "byte"}
    return None          # custom tokenizers don't round-trip; pass anew


def _tokenizer_from_spec(spec: dict | None):
    if not spec:
        return None
    from .data.tokenizer import ByteTokenizer, HashWordTokenizer
    if spec["kind"] == "hash_word":
        return HashWordTokenizer(vocab=spec["vocab"],
                                 lowercase=spec["lowercase"])
    if spec["kind"] == "byte":
        return ByteTokenizer()
    return None


__all__ = ["Aligner", "AlignerConfig", "WeightFn", "Alignment",
           "Match", "QueryResult", "QueryOptions",
           "SearchIndex", "IndexBuilder", "LiveIndex"]
