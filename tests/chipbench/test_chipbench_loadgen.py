"""The load generator against a stub server that sleeps a fixed time."""

import asyncio
import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SLEEP_S = 0.05


class StubServer:
    """POST /query answers after SLEEP_S; requests that arrive while a
    stall is set are held until the stall ends."""

    def __init__(self):
        self.stall = None                     # (start, end), monotonic
        self.loop = asyncio.new_event_loop()
        self.ready = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        asyncio.set_event_loop(self.loop)
        self.server = self.loop.run_until_complete(
            asyncio.start_server(self._conn, "127.0.0.1", 0))
        self.port = self.server.sockets[0].getsockname()[1]
        self.ready.set()
        self.loop.run_forever()

    async def _conn(self, reader, writer):
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                length = 0
                while True:
                    h = await reader.readline()
                    if h in (b"\r\n", b""):
                        break
                    if h.lower().startswith(b"content-length:"):
                        length = int(h.split(b":")[1])
                await reader.readexactly(length)
                now = time.monotonic()
                if self.stall and self.stall[0] <= now < self.stall[1]:
                    await asyncio.sleep(self.stall[1] - now)
                await asyncio.sleep(SLEEP_S)
                body = b'{"ok": true, "result": {"matches": []}}'
                writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: " +
                             str(len(body)).encode() + b"\r\n\r\n" + body)
                await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            writer.close()

    def __enter__(self):
        self.thread.start()
        assert self.ready.wait(10)
        return self

    def __exit__(self, *exc):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)
        assert not self.thread.is_alive()


def _files(tmp_path, traffic: dict) -> tuple[Path, Path]:
    cfg = {"name": "tiny", "corpus_tokens": 2000,
           "corpus": {"vocab": 500, "zipf_s": 1.1, "doc_len": [64, 128],
                      "corpus_seed": 1}}
    c, t = tmp_path / "cfg.json", tmp_path / "traffic.json"
    c.write_text(json.dumps(cfg))
    t.write_text(json.dumps({"shape_seed": 3, "planted_share": 0.5,
                             "edit_rate": [0.0, 0.1],
                             "edit_ops": ["substitute"],
                             "length": {"dist": "fixed", "min": 20,
                                        "max": 20}, **traffic}))
    return c, t


def _run(tmp_path, port, traffic: dict, seconds: float, on_start=None):
    cfg, tr = _files(tmp_path, traffic)
    out = tmp_path / "records.jsonl"
    spec = {"config": str(cfg), "traffic": str(tr), "seed": 2 ** 31 + 7,
            "seconds": seconds, "theta": 0.8,
            "options": {"plan": "device"}, "grace_s": 10.0,
            "out": str(out)}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    child = subprocess.Popen(
        [sys.executable, str(ROOT / "chipbench" / "loadgen.py"),
         str(tmp_path / "spec.json")], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == "READY"
        child.stdin.write(f"GO {port}\n")
        child.stdin.flush()
        start = child.stdout.readline().split()
        assert start[0] == "START"
        if on_start:
            on_start(float(start[1]))
        rest = child.stdout.read()
        assert child.wait(timeout=60) == 0
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    assert rest.strip().endswith("END")
    assert "LATE" in rest
    return float(start[1]), [json.loads(x) for x in
                             out.read_text().splitlines()]


def test_loadgen_never_imports_jax():
    code = ("import sys; sys.argv = ['x']; "
            f"sys.path.insert(0, {str(ROOT)!r}); "
            "import chipbench.loadgen; "
            "assert 'jax' not in sys.modules, 'loadgen imported jax'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


@pytest.mark.parametrize("clients", [2, 4])
def test_closed_loop_throughput_is_clients_over_sleep(tmp_path, clients):
    seconds = 1.5
    with StubServer() as stub:
        t0, recs = _run(tmp_path, stub.port,
                        {"loop": "closed", "clients": clients}, seconds)
    done = [r for r in recs if r["status"] == 200 and
            r["done"] <= t0 + seconds]
    rate = len(done) / seconds
    assert rate == pytest.approx(clients / SLEEP_S, rel=0.2)
    assert len({r["n"] for r in recs}) == len(recs)      # never repeated
    for r in recs:
        assert r["done"] - r["due"] >= SLEEP_S * 0.99


def test_open_loop_stall_shows_in_every_request_due_during_it(tmp_path):
    seconds, stall = 2.0, (0.6, 1.2)
    with StubServer() as stub:
        def arm(t0):
            stub.stall = (t0 + stall[0], t0 + stall[1])
        t0, recs = _run(tmp_path, stub.port,
                        {"loop": "open", "rate_qps": 25.0,
                         "connections": 4}, seconds, on_start=arm)
    assert len(recs) == 50 and all(r["status"] == 200 for r in recs)
    end = t0 + stall[1]
    during = [r for r in recs if t0 + stall[0] <= r["due"] < end]
    assert len(during) >= 8
    for r in during:
        assert r["done"] >= end + SLEEP_S * 0.99           # waited it out
        assert r["done"] - r["due"] >= end - r["due"]
    before = [r for r in recs if r["due"] < t0 + stall[0] - 0.2]
    assert before and max(r["done"] - r["due"] for r in before) < 0.5
