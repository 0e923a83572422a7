"""The asyncio front end: HTTP/1.1 + WebSocket endpoints over the
dynamic batcher, plus the graceful-compaction orchestration.

Pure stdlib on purpose — the repo's dependency surface is numpy+jax, and
an alignment query server needs exactly six endpoints:

====== ========== ===================================================
POST   /query     one alignment query (dynamic-batched); body/response
                  per :mod:`repro.serve.protocol`
POST   /add       index one document into the live delta (FIFO with
                  queries: later queries see it); with a WAL open the
                  200 is sent only after the record is fsync-durable,
                  and a client ``request_id`` makes retries idempotent
POST   /compact   fold the delta into a new store generation without
                  pausing traffic (see :meth:`AlignServer.compact`)
GET    /metrics   :class:`~repro.serve.metrics.ServeMetrics` snapshot
GET    /healthz   liveness + serving generation
GET    /ws        WebSocket upgrade; each text frame is one /query
                  body, responses fan back per-message (pipelined)
====== ========== ===================================================

Graceful generation swap: ``/compact`` never stops the world.  The
engine thread seals the delta (one pointer swap between batches), a
background thread merges frozen + sealed into a new ``v{N:06d}``
generation — reading only immutable state while queries keep batching
against (frozen, sealed, fresh delta) — and the engine thread promotes
the ``CURRENT`` pointer between two batches.  A query in flight when the
promotion lands was dispatched against the old references and completes
against them; the next batch sees the new generation.  Local text ids
are stable across the swap, so the two views are bit-identical.
"""

from __future__ import annotations

import asyncio
import base64
import hashlib
import json
import struct
from contextlib import contextmanager

from .. import fault
from ..core.live import LiveIndex
from ..core.sharded_index import ShardedAlignmentIndex
from ..core.spans import span
from ..core.store import store_counters
from .batcher import DeadlineExceeded, DynamicBatcher, QueueFull
from .metrics import ServeMetrics
from .protocol import (ProtocolError, error_response, ok_response,
                       parse_add_request, parse_query_request)

_WS_GUID = "258EAFA5-E914-47DA-95CA-C5AB0DC85B11"
_REASONS = {200: "OK", 400: "Bad Request", 404: "Not Found",
            405: "Method Not Allowed", 409: "Conflict",
            500: "Internal Server Error", 503: "Service Unavailable",
            504: "Gateway Timeout"}


class AlignServer:
    """One Aligner behind an asyncio TCP server with dynamic batching."""

    def __init__(self, aligner, *, host: str = "127.0.0.1", port: int = 0,
                 max_batch: int = 32, max_linger_us: float = 2000.0,
                 queue_cap: int = 256, retry_after_s: float = 1.0,
                 supervisor=None):
        self.aligner = aligner
        self.host = host
        self.port = port
        self.metrics = ServeMetrics()
        self.batcher = DynamicBatcher(aligner, max_batch=max_batch,
                                      max_linger_us=max_linger_us,
                                      queue_cap=queue_cap,
                                      metrics=self.metrics)
        idx = aligner._index
        if isinstance(idx, LiveIndex) and idx.wal is not None:
            # durable-ack hook: adds coalesce into write groups and the
            # batcher runs ONE wal fsync per group before resolving any
            # of their futures — the linger window IS the commit window
            self.batcher.write_flush = idx.wal_commit
        # advisory Retry-After on admission-control 503s (seconds)
        self.retry_after_s = retry_after_s
        # optional CompactionSupervisor (serve.supervisor); started and
        # stopped with the server's own lifecycle
        self.supervisor = supervisor
        self._server: asyncio.AbstractServer | None = None
        self._compacting = False
        # shard ids the most recent degraded fan-out skipped (empty while
        # healthy); drives the /healthz healthy|degraded status
        self._last_failed_shards: tuple = ()

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> "AlignServer":
        self.batcher.start()
        self._server = await asyncio.start_server(self._handle_conn,
                                                  self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        if self.supervisor is not None:
            self.supervisor.bind(self)
            self.supervisor.start()
        return self

    async def close(self) -> None:
        if self.supervisor is not None:
            await self.supervisor.close()
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None
        await self.batcher.close()

    async def __aenter__(self) -> "AlignServer":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.close()

    async def serve_forever(self) -> None:
        await self.start()
        try:
            await self._server.serve_forever()
        finally:
            await self.close()

    # -- endpoint bodies (shared by HTTP and WebSocket) ----------------------

    @contextmanager
    def _span(self, name: str):
        """A front-end span on the event loop: annotated and timed as the
        engine's are (:mod:`repro.core.spans`), its seconds handed to the
        metrics under their lock, never into a batch's stage dict."""
        times: dict = {}
        try:
            with span(times, name):
                yield
        finally:
            self.metrics.observe_span(name, times[name])

    async def handle_query(self, body) -> tuple[int, bytes]:
        with self._span("serve.parse"):
            try:
                req = parse_query_request(body)
                tokens = self.aligner._tokens(req.text)
            except (ProtocolError, ValueError) as e:
                return 400, error_response(str(e), 400)

        def err(message: str, status: int) -> tuple[int, bytes]:
            # errors echo the client's id too, so pipelined WebSocket
            # clients can correlate every outcome
            d = json.loads(error_response(message, status))
            if req.id is not None:
                d["id"] = req.id
            return status, json.dumps(d).encode()

        try:
            fut = self.batcher.submit_query(tokens, req.theta, req.options,
                                            deadline_s=req.deadline_s)
        except QueueFull as e:
            return err(str(e), 503)
        try:
            result = await fut
        except DeadlineExceeded as e:
            return err(str(e), 504)
        except asyncio.CancelledError:
            raise
        except Exception as e:                      # noqa: BLE001
            return err(f"{type(e).__name__}: {e}", 500)
        if result.degraded:
            self.metrics.inc("degraded_total")
            self._last_failed_shards = tuple(result.failed_shards)
        else:
            self._last_failed_shards = ()
        with self._span("serve.respond"):
            payload = {"result": result.to_dict()}
            if req.id is not None:
                payload["id"] = req.id
            return 200, ok_response(payload)

    async def handle_add(self, body) -> tuple[int, bytes]:
        try:
            text, request_id = parse_add_request(body)
            tokens = self.aligner._tokens(text)
        except (ProtocolError, ValueError) as e:
            return 400, error_response(str(e), 400)

        def _do_add():
            # the dedup window answers replayed request_ids without
            # growing the corpus — detect that by the doc count
            before = self.aligner.num_docs
            gid = self.aligner.add(tokens, request_id=request_id)
            return gid, self.aligner.num_docs == before

        try:
            # add mutates the delta, so it is @engine_only: calling
            # aligner.add() here directly would race the batch in flight
            # (RPR101 flags it).  With a WAL wired, submit_write groups
            # consecutive adds and acks only after the group's single
            # wal fsync (write_flush); without one, submit_control keeps
            # the plain FIFO path.
            if self.batcher.write_flush is not None:
                doc_id, deduped = await self.batcher.submit_write(_do_add)
            else:
                doc_id, deduped = await self.batcher.submit_control(
                    _do_add, "add")
        except RuntimeError as e:       # frozen (non-live) index
            return 409, error_response(str(e), 409)
        self.metrics.inc("adds_deduped_total" if deduped else "adds_total")
        return 200, ok_response({"doc_id": int(doc_id),
                                 "deduped": bool(deduped)})

    async def handle_compact(self) -> tuple[int, bytes]:
        try:
            gen = await self.compact()
        except RuntimeError as e:
            return 409, error_response(str(e), 409)
        return 200, ok_response({"generation": int(gen)})

    async def compact(self) -> int:
        """Fold the live delta into a new promoted store generation
        WITHOUT pausing traffic (seal on engine → merge off-band →
        promote on engine); returns the serving generation.

        Every index touch below rides a dispatcher: ``seal_delta`` and
        ``promote_sealed`` are ``@engine_only`` (RPR101) and go through
        ``submit_control``; ``merge_sealed`` reads only immutable state
        and runs via ``run_offband`` so serving never pauses."""
        idx = self.aligner._index
        if isinstance(idx, ShardedAlignmentIndex):
            # per-shard deltas: run the whole fold as one engine op (it
            # blocks batches for its duration; the overlapped path below
            # is the flat live store's)
            await self.batcher.submit_control(idx.compact, "compact")
            self.metrics.inc("compactions_total")
            return max((s.generation for s in idx.shards
                        if getattr(s, "is_live", False)), default=0)
        if not isinstance(idx, LiveIndex):
            raise RuntimeError(
                "this server holds a frozen index; load the store with "
                "live=True to take writes and compactions")
        if self._compacting:
            raise RuntimeError("a compaction is already in progress")
        self._compacting = True
        try:
            def _seal():
                if idx.sealed is None and idx.delta.num_texts == 0:
                    return False         # nothing to fold in
                if idx.sealed is None:
                    idx.seal_delta()
                return True

            if not await self.batcher.submit_control(_seal, "seal"):
                return idx.generation
            gen, new_idx = await self.batcher.run_offband(idx.merge_sealed)
            await self.batcher.submit_control(
                lambda: idx.promote_sealed(gen, new_idx), "promote")
            self.metrics.inc("compactions_total")
            return gen
        finally:
            self._compacting = False

    def _healthz(self) -> bytes:
        idx = self.aligner._index
        gen = getattr(idx, "generation", None)
        degraded = bool(self._last_failed_shards) or \
            (self.supervisor is not None and self.supervisor.failing)
        payload = {"status": "degraded" if degraded else "healthy",
                   "docs": self.aligner.num_docs,
                   "generation": gen,
                   "live": isinstance(idx, LiveIndex),
                   "compacting": self._compacting,
                   "failed_shards": list(self._last_failed_shards)}
        if isinstance(idx, LiveIndex):
            # compaction-pressure gauges plus the ingest-durability view:
            # wal.lag_records is what a crash right now would replay
            payload["delta_fraction"] = idx.delta_fraction
            payload["delta_age_s"] = idx.delta_age_s
            wal = idx.wal_status()
            if wal is not None:
                payload["wal"] = {"replayed": wal["replayed"],
                                  "lag_records": wal["lag_records"],
                                  "pending_records": wal["pending"],
                                  "bytes": wal["bytes"],
                                  "age_s": wal["age_s"]}
        return ok_response(payload)

    # -- HTTP plumbing -------------------------------------------------------

    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                parsed = await self._read_request(reader)
                if parsed is None:
                    break
                method, path, headers, body = parsed
                if path == "/ws" and \
                        "websocket" in headers.get("upgrade", "").lower():
                    await self._ws_session(reader, writer, headers)
                    break
                status, payload = await self._route(method, path, body)
                close = headers.get("connection", "").lower() == "close"
                retry_after = self.retry_after_s if status == 503 else None
                writer.write(_http_response(status, payload, close=close,
                                            retry_after_s=retry_after))
                await writer.drain()
                if close:
                    break
        except (asyncio.IncompleteReadError, ConnectionResetError,
                BrokenPipeError):
            pass
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _route(self, method: str, path: str, body: bytes
                     ) -> tuple[int, bytes]:
        if path == "/query" and method == "POST":
            return await self.handle_query(body)
        if path == "/add" and method == "POST":
            return await self.handle_add(body)
        if path == "/compact" and method == "POST":
            return await self.handle_compact()
        if path == "/metrics" and method == "GET":
            snap = self.metrics.snapshot()
            snap["fault"] = fault.stats()
            snap["store"] = store_counters()
            idx = self.aligner._index
            if isinstance(idx, LiveIndex):
                snap["wal"] = idx.wal_status()
            return 200, json.dumps(snap).encode()
        if path == "/healthz" and method == "GET":
            return 200, self._healthz()
        if path in ("/query", "/add", "/compact", "/metrics", "/healthz"):
            return 405, error_response(f"{method} not allowed on {path}",
                                       405)
        return 404, error_response(f"no such endpoint: {path}", 404)

    @staticmethod
    async def _read_request(reader):
        line = await reader.readline()
        if not line:
            return None
        try:
            method, path, _version = line.decode("latin-1").split()
        except ValueError:
            return None
        headers = {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            key, _, val = h.decode("latin-1").partition(":")
            headers[key.strip().lower()] = val.strip()
        n = int(headers.get("content-length", 0) or 0)
        body = await reader.readexactly(n) if n else b""
        return method, path.split("?", 1)[0], headers, body

    # -- WebSocket (RFC 6455, text frames) -----------------------------------

    async def _ws_session(self, reader, writer, headers) -> None:
        key = headers.get("sec-websocket-key")
        if not key:
            writer.write(_http_response(
                400, error_response("missing Sec-WebSocket-Key", 400),
                close=True))
            await writer.drain()
            return
        accept = base64.b64encode(hashlib.sha1(
            (key + _WS_GUID).encode()).digest()).decode()
        writer.write(("HTTP/1.1 101 Switching Protocols\r\n"
                      "Upgrade: websocket\r\n"
                      "Connection: Upgrade\r\n"
                      f"Sec-WebSocket-Accept: {accept}\r\n\r\n").encode())
        await writer.drain()
        send_lock = asyncio.Lock()
        tasks: set[asyncio.Task] = set()

        async def answer(payload: bytes):
            try:
                _status, resp = await self.handle_query(payload)
                async with send_lock:
                    writer.write(_ws_frame(0x1, resp))
                    await writer.drain()
            except (ConnectionResetError, BrokenPipeError):
                pass

        try:
            while True:
                frame = await _ws_read_frame(reader)
                if frame is None:
                    break
                opcode, payload = frame
                if opcode == 0x8:                        # close
                    async with send_lock:
                        writer.write(_ws_frame(0x8, payload[:2]))
                        await writer.drain()
                    break
                if opcode == 0x9:                        # ping -> pong
                    async with send_lock:
                        writer.write(_ws_frame(0xA, payload))
                        await writer.drain()
                    continue
                if opcode == 0xA:                        # stray pong
                    continue
                # text (or binary) frame: one query; answer out-of-band so
                # the socket pipelines many in-flight queries
                t = asyncio.get_running_loop().create_task(answer(payload))
                tasks.add(t)
                t.add_done_callback(tasks.discard)
            if tasks:
                await asyncio.gather(*tasks, return_exceptions=True)
        finally:
            for t in tasks:
                t.cancel()


def _http_response(status: int, body: bytes, *, close: bool = False,
                   retry_after_s: float | None = None) -> bytes:
    head = (f"HTTP/1.1 {status} {_REASONS.get(status, 'Unknown')}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n")
    if retry_after_s is not None:
        # advisory backoff for admission-control 503s (RFC 9110 §10.2.3;
        # delta-seconds form, fractional values are tolerated by our client)
        head += f"Retry-After: {retry_after_s:g}\r\n"
    head += f"Connection: {'close' if close else 'keep-alive'}\r\n\r\n"
    return head.encode("latin-1") + body


def _ws_frame(opcode: int, payload: bytes) -> bytes:
    """One server->client frame (fin=1, unmasked)."""
    head = bytes([0x80 | opcode])
    n = len(payload)
    if n < 126:
        head += bytes([n])
    elif n < (1 << 16):
        head += bytes([126]) + struct.pack("!H", n)
    else:
        head += bytes([127]) + struct.pack("!Q", n)
    return head + payload


async def _ws_read_frame(reader) -> tuple[int, bytes] | None:
    """One client->server frame; unmasks, rejects fragmentation (each
    protocol message fits one frame)."""
    try:
        b0, b1 = await reader.readexactly(2)
    except asyncio.IncompleteReadError:
        return None
    fin, opcode = b0 & 0x80, b0 & 0x0F
    if not fin or opcode == 0x0:
        raise ConnectionResetError("fragmented WebSocket frames are not "
                                   "supported by this server")
    masked, n = b1 & 0x80, b1 & 0x7F
    if n == 126:
        n = struct.unpack("!H", await reader.readexactly(2))[0]
    elif n == 127:
        n = struct.unpack("!Q", await reader.readexactly(8))[0]
    mask = await reader.readexactly(4) if masked else None
    payload = await reader.readexactly(n) if n else b""
    if mask:
        payload = bytes(c ^ mask[i % 4] for i, c in enumerate(payload))
    return opcode, payload
