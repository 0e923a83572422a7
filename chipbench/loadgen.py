#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX.

    python chipbench/loadgen.py SPEC.json

SPEC names the configuration and traffic files, the seed, the window's
seconds, theta, the query options and the file to write.  The generator
regenerates the corpus from the configuration (to plant spans without the
index), builds the requests the window will send (every one of an open
loop; the first ``PREBUILT`` of a closed loop), prints ``READY``, and
starts the window when it reads ``GO <port>`` on standard input:

* closed loop: ``clients`` connections, each sending its next query when
  the last one is answered, until the window closes;
* open loop: one request at each due time of the traffic, on a free
  keep-alive connection (a new one when none is free).

Every request is timed from its due time (in a closed loop, the moment
the client sends it), so a stall counts against every request due during
it.  Requests still open when the window closes are awaited up to
``grace_s`` more; one that does not come back by then counts with status
0.  Query ``n`` is the traffic's query ``n``: never one twice in a run.
It prints ``START <t0>``, then how late it sent (``LATE ...``), then
``END`` once the records (one JSON line per request) are written.
"""

from __future__ import annotations

import asyncio
import itertools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.workload import Traffic, make_corpus  # noqa: E402

PREBUILT = 1024        # closed-loop requests built before the window opens


class Conn:
    """One keep-alive HTTP/1.1 connection."""

    def __init__(self, reader, writer):
        self.reader, self.writer = reader, writer

    @classmethod
    async def open(cls, host: str, port: int) -> "Conn":
        return cls(*await asyncio.open_connection(host, port))

    async def post(self, path: str, body: bytes) -> tuple[int, bytes]:
        self.writer.write(
            f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await self.writer.drain()
        status = int((await self.reader.readline()).split()[1])
        length = 0
        while True:
            line = await self.reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, val = line.decode("latin-1").partition(":")
            if key.strip().lower() == "content-length":
                length = int(val)
        return status, await self.reader.readexactly(length)

    def close(self) -> None:
        self.writer.close()


class LoadGen:
    def __init__(self, spec: dict):
        self.spec = spec
        cfg = json.loads(Path(spec["config"]).read_text())
        self.traffic_spec = json.loads(Path(spec["traffic"]).read_text())
        self.traffic = Traffic(cfg, self.traffic_spec, spec["seed"],
                               spec["seconds"], make_corpus(cfg))
        self.records: list[dict] = []
        self.idle: list[Conn] = []
        # no query is drawn in the window: an open loop's are all known,
        # and a closed loop sends PREBUILT before it needs another
        due = self.traffic.due
        self.bodies = [self.body(self.traffic.query(n)[0]) for n in
                       range(PREBUILT if due is None else len(due))]
        self.port = 0

    def body(self, tokens) -> bytes:
        return json.dumps({"text": [int(t) for t in tokens],
                           "theta": self.spec["theta"],
                           "options": self.spec["options"]}).encode()

    async def send(self, conn: Conn, n: int, body: bytes, due: float,
                   giveup: float) -> bool:
        """One request; records it and says whether the connection can
        be used again."""
        sent = time.monotonic()
        rec = {"n": n, "due": due, "sent": sent}
        try:
            status, payload = await asyncio.wait_for(
                conn.post("/query", body), max(0.0, giveup - sent))
        except (asyncio.TimeoutError, ConnectionError,
                asyncio.IncompleteReadError, ValueError, IndexError):
            rec.update(done=None, status=0)
            self.records.append(rec)
            conn.close()
            return False
        rec.update(done=time.monotonic(), status=status)
        if status == 200:
            rec["body"] = payload.decode()
        self.records.append(rec)
        return True

    async def closed(self, t0: float, t_end: float, giveup: float) -> None:
        counter = itertools.count()

        async def client(conn: Conn):
            while time.monotonic() < t_end:
                n = next(counter)
                body = (self.bodies[n] if n < len(self.bodies) else
                        self.body(self.traffic.query(n)[0]))
                if not await self.send(conn, n, body, time.monotonic(),
                                       giveup):
                    return

        await asyncio.gather(*(client(c) for c in self.idle))

    async def open(self, t0: float, giveup: float) -> None:
        host, port = "127.0.0.1", self.port
        tasks = []

        async def one(n: int, due: float):
            conn = self.idle.pop() if self.idle else \
                await Conn.open(host, port)
            if await self.send(conn, n, self.bodies[n], due, giveup):
                self.idle.append(conn)

        for n, offset in enumerate(self.traffic.due):
            due = t0 + float(offset)
            wait = due - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            tasks.append(asyncio.create_task(one(n, due)))
        await asyncio.gather(*tasks)

    async def run(self) -> None:
        spec, tr = self.spec, self.traffic_spec
        conns = tr["clients"] if tr["loop"] == "closed" else tr["connections"]
        print("READY", flush=True)
        loop = asyncio.get_running_loop()
        go = await loop.run_in_executor(None, sys.stdin.readline)
        self.port = int(go.split()[1])                             # GO <port>
        self.idle = [await Conn.open("127.0.0.1", self.port)
                     for _ in range(conns)]
        t0 = time.monotonic()
        t_end = t0 + spec["seconds"]
        giveup = t_end + spec["grace_s"]
        print(f"START {t0!r}", flush=True)
        if tr["loop"] == "closed":
            await self.closed(t0, t_end, giveup)
        else:
            await self.open(t0, giveup)
        for c in self.idle:
            c.close()
        late = sorted(r["sent"] - r["due"] for r in self.records)
        if late:
            print(f"LATE sends {len(late)}, median {late[len(late) // 2]!r} "
                  f"s, max {late[-1]!r} s", flush=True)
        with open(spec["out"], "w") as f:
            for r in self.records:
                f.write(json.dumps(r) + "\n")
        print("END", flush=True)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    spec = json.loads(Path(argv[0]).read_text())
    t0 = time.monotonic()
    gen = LoadGen(spec)
    print(f"loadgen: {len(gen.bodies)} requests built in "
          f"{time.monotonic() - t0!r} s", file=sys.stderr, flush=True)
    asyncio.run(gen.run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
