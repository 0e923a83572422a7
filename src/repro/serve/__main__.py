"""Serve CLI: run an align server over a saved index store.

    PYTHONPATH=src python -m repro.serve --store idx_dir --live \
        --port 8080 --max-batch 32 --linger-us 2000

``--live`` opens the store for incremental serving (POST /add and
POST /compact work); without it the server is query-only.

``--wal`` (requires ``--live``) makes ingest durable: every /add is
logged to a write-ahead log before it is indexed and acknowledged only
after its record is fsynced.  The default policy is group commit — the
batcher runs one fsync per write micro-batch, so its linger window is
the commit window; ``--wal-fsync-every-n 1`` forces an fsync per record
instead.
"""

from __future__ import annotations

import argparse
import asyncio


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        "python -m repro.serve",
        description="asyncio alignment server with dynamic batching")
    ap.add_argument("--store", required=True,
                    help="index store directory (Aligner.save / build store=)")
    ap.add_argument("--live", action="store_true",
                    help="open live: accept /add writes and /compact")
    ap.add_argument("--no-mmap", action="store_true",
                    help="materialize the index instead of mmap-serving it")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--max-batch", type=int, default=32,
                    help="dynamic batch size cap (default 32)")
    ap.add_argument("--linger-us", type=float, default=2000.0,
                    help="max micro-batch linger in microseconds")
    ap.add_argument("--queue-cap", type=int, default=256,
                    help="in-flight request cap; beyond it requests get 503")
    ap.add_argument("--auto-compact", action="store_true",
                    help="run a CompactionSupervisor: background "
                         "seal/merge/promote when the delta grows or ages "
                         "past the thresholds below (live stores only)")
    ap.add_argument("--compact-fraction", type=float, default=0.25,
                    help="compact when delta docs exceed this fraction of "
                         "the total (default 0.25)")
    ap.add_argument("--compact-age-s", type=float, default=30.0,
                    help="compact when the oldest delta doc is this old "
                         "(default 30s)")
    ap.add_argument("--prune-keep", type=int, default=2,
                    help="superseded store generations to retain after each "
                         "background compaction (default 2)")
    ap.add_argument("--wal", action="store_true",
                    help="durable ingest (flat live stores only): log every "
                         "/add to a write-ahead log and ack only after its "
                         "record is fsynced; crash replay restores every "
                         "acknowledged write")
    ap.add_argument("--wal-fsync-every-n", type=int, default=0,
                    help="WAL fsync policy: 0 (default) = group commit, one "
                         "fsync per batcher write micro-batch; 1 = fsync "
                         "every record; N>1 = fsync every N records")
    ap.add_argument("--wal-segment-bytes", type=int, default=4 << 20,
                    help="WAL segment rotation size (default 4 MiB)")
    ap.add_argument("--wal-max-bytes", type=int, default=32_000_000,
                    help="with --auto-compact: compact when un-truncated WAL "
                         "segments exceed this many bytes (default 32e6)")
    ap.add_argument("--wal-max-age-s", type=float, default=60.0,
                    help="with --auto-compact: compact when the oldest "
                         "un-compacted WAL record is this old (default 60s)")
    args = ap.parse_args(argv)

    from repro.api import Aligner
    from repro.compile_cache import configure_compile_cache
    from repro.serve import AlignServer, CompactionSupervisor

    configure_compile_cache()

    wal = False
    if args.wal:
        if not args.live:
            ap.error("--wal requires --live")
        from repro.wal import WalConfig
        wal = WalConfig(fsync_every_n=args.wal_fsync_every_n,
                        segment_bytes=args.wal_segment_bytes)
    # WAL replay inside load() indexes into the delta, but this runs at
    # startup before the server (and its engine thread) exists
    aligner = Aligner.load(args.store, mmap=not args.no_mmap,  # repro: allow[RPR101]
                           live=args.live, wal=wal)
    print(f"serving {aligner!r}")

    supervisor = None
    if args.auto_compact:
        supervisor = CompactionSupervisor(
            max_delta_fraction=args.compact_fraction,
            max_delta_age_s=args.compact_age_s,
            prune_keep=args.prune_keep,
            max_wal_bytes=args.wal_max_bytes,
            max_wal_age_s=args.wal_max_age_s)

    async def run():
        server = AlignServer(aligner, host=args.host, port=args.port,
                             max_batch=args.max_batch,
                             max_linger_us=args.linger_us,
                             queue_cap=args.queue_cap,
                             supervisor=supervisor)
        await server.start()
        print(f"listening on http://{server.host}:{server.port} "
              f"(endpoints: /query /add /compact /metrics /healthz /ws)")
        try:
            await server._server.serve_forever()
        finally:
            await server.close()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
