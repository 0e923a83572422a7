#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell names a configuration (``chipbench/configs/<config>.json``: the
deployment, its corpus and its guarantees) and a traffic mix
(``chipbench/traffic/<mix>.json``).  A run

1. loads the configuration's store with ``Aligner.load(store, mmap=True)``
   (building it into ``chipbench/.cache/store/`` first when this checkout
   has not built it yet: the corpus is fixed per configuration) and
   uploads its probe arena to the chip;
2. warms every probe batch size this cell can send and a few batches of
   the cell's own traffic, drawn from a seed the window does not use;
3. serves the store through ``repro.serve.AlignServer`` on localhost, every
   request with ``"options": {"plan": "device"}``, to the load generator
   (``chipbench/loadgen.py``, a child process that never imports JAX; it
   starts with the run and builds its requests while the chip is brought
   up) for ``--seconds``; with ``--trace 1`` under ``jax.profiler``;
4. reads the chip's peak memory, frees the server and the store, and
   checks the window's answers against the plain reference
   (``chipbench/reference/``): every answer due must come, and a sample
   drawn from the seed (every planted query, then others) must be the
   reference's, match for match and cell for cell;
5. prints the metrics: the cell's end-to-end metrics with ``--trace 0``,
   its per-layer metrics with ``--trace 1``.  Each metric is read by
   ``chipbench/metrics/<name>.py`` (the name up to its first dot) from
   the run's record.

The last line on standard output is one JSON object; the numbers compared
for ``correct`` come last there and as the last lines on standard error.
On a machine where JAX finds no TPU, or fewer chips than the cell asks
for, the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.reference import block_rows  # noqa: E402
from chipbench.workload import Traffic, make_corpus  # noqa: E402

SPAN = "chipbench.find_batch"
CHECK_SAMPLE = 48          # answers compared with the reference per run
GRACE_S = 60.0             # how long past the window an answer may come
WARM_SEED = 1 << 100       # warm-up traffic: a seed no run uses
WARM_BATCHES = 2


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# BENCHMARK.json, and the files it names
# --------------------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    config_path: Path
    traffic: dict
    traffic_path: Path
    end_to_end: list
    per_layer: list
    root: Path


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its
    configuration and traffic files and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; cells: "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config_path = root / conf["file"]
    traffic_path = root / "chipbench" / "traffic" / f"{w['traffic']}.json"
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", ()) or
             ("workloads" not in m and m["moves"] in names)]
    return Cell(name=name, chips=w["chips"],
                config=json.loads(config_path.read_text()),
                config_path=config_path,
                traffic=json.loads(traffic_path.read_text()),
                traffic_path=traffic_path, end_to_end=e2e, per_layer=layer,
                root=root)


def load_reader(root: Path, metric: str):
    """``read(record) -> float | None`` of ``chipbench/metrics/<base>.py``,
    where ``base`` is the metric's name up to its first dot."""
    base = metric.split(".")[0]
    path = root / "chipbench" / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def max_batch(cell: Cell) -> int:
    """The largest batch this cell's traffic can form."""
    cap = cell.config["server"]["max_batch"]
    if cell.traffic["loop"] == "closed":
        return min(cap, cell.traffic["clients"])
    return cap


# --------------------------------------------------------------------------
# set-up: store, upload, warm-up
# --------------------------------------------------------------------------


def ensure_store(cell: Cell) -> Path:
    """The configuration's store, built once per checkout (by
    ``chipbench/build_store.py``, in a process of its own) and keyed by a
    digest of the configuration file."""
    digest = hashlib.sha256(cell.config_path.read_bytes()).hexdigest()[:16]
    cache = cell.root / "chipbench" / ".cache" / "store"
    store = cache / f"{cell.config['name']}-{digest}"
    if (store / "done").exists():
        return store
    part = cache / f"{cell.config['name']}-{digest}.part"
    shutil.rmtree(part, ignore_errors=True)
    part.mkdir(parents=True)
    subprocess.run([sys.executable,
                    str(cell.root / "chipbench" / "build_store.py"),
                    str(cell.config_path), str(part / "store")],
                   env=dict(os.environ, JAX_PLATFORMS="cpu"), check=True)
    (part / "done").write_text("")
    shutil.rmtree(store, ignore_errors=True)
    os.replace(part, store)
    return store


def warm_up(aligner, cell: Cell, docs, seconds: float) -> None:
    """Compile every probe batch size P = B * k this cell can send, then
    run a few batches of its own traffic from a seed the window does not
    use."""
    from repro.api import QueryOptions
    cfg = cell.config
    opts = QueryOptions(plan="device")
    rare = [cfg["corpus"]["vocab"] - 1, cfg["corpus"]["vocab"] - 2]
    for b in range(1, max_batch(cell) + 1):
        aligner.find_batch([rare] * b, cfg["theta"], options=opts)
    # natural draws: the work strata shape only the window's traffic
    warm = Traffic(cfg, dict(cell.traffic, strata=0), WARM_SEED, seconds,
                   docs)
    b = min(max_batch(cell), 8)
    for i in range(WARM_BATCHES):
        aligner.find_batch([warm.query(i * b + j)[0] for j in range(b)],
                           cfg["theta"], options=opts)


class CompileCounter:
    """Backend compiles seen by JAX's monitoring hook while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n, self.seconds, self.on = 0, 0.0, False

    def __enter__(self) -> "CompileCounter":
        import jax
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        self.on = False
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.on and event == self.EVENT:
            self.n += 1
            self.seconds += duration


class TracedAligner:
    """The aligner as the server sees it, with each ``find_batch`` inside
    the ``chipbench.find_batch`` host span and its batch size noted."""

    def __init__(self, aligner):
        self._aligner = aligner
        self.batches: list[int] = []

    def __getattr__(self, name):
        return getattr(self._aligner, name)

    def find_batch(self, texts, theta, **kw):
        import jax
        self.batches.append(len(texts))
        with jax.profiler.TraceAnnotation(SPAN):
            return self._aligner.find_batch(texts, theta, **kw)


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------


class LoadGenChild:
    """``chipbench/loadgen.py`` as a child process, started before set-up
    so that it builds its requests while the chip is brought up; it opens
    the window when it is told the server's port."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 options: dict):
        self.dir = Path(tempfile.mkdtemp(prefix="chipbench-loadgen-"))
        self.out = self.dir / "records.jsonl"
        spec = {"config": str(cell.config_path),
                "traffic": str(cell.traffic_path), "seed": seed,
                "seconds": seconds, "theta": cell.config["theta"],
                "options": options, "grace_s": GRACE_S, "out": str(self.out)}
        (self.dir / "spec.json").write_text(json.dumps(spec))
        self.proc = subprocess.Popen(
            [sys.executable, str(cell.root / "chipbench" / "loadgen.py"),
             str(self.dir / "spec.json")], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)

    async def line(self) -> str:
        loop = asyncio.get_running_loop()
        return (await loop.run_in_executor(None, self.proc.stdout.readline)
                ).strip()

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        shutil.rmtree(self.dir, ignore_errors=True)


def _serve_snapshot(metrics) -> dict:
    from repro.core.device_plan import transfer_stats
    with metrics._lock:
        return {"stage_seconds": dict(metrics.stage_seconds),
                "batches": metrics.batch_size.total,
                "queries": metrics.batch_size.sum,
                "counters": dict(metrics.counters),
                "transfer": transfer_stats()}


async def serve_window(aligner, cell: Cell, loadgen: LoadGenChild,
                       trace_dir: str | None) -> dict:
    """Serve the window to the load generator; the run's record of it."""
    import jax

    from repro.serve import AlignServer
    server_cfg = cell.config["server"]
    served = TracedAligner(aligner)
    server = AlignServer(served, host="127.0.0.1", port=0,
                         max_batch=server_cfg["max_batch"],
                         max_linger_us=server_cfg["max_linger_us"])
    await server.start()
    child = loadgen.proc
    compiles = CompileCounter()
    rec: dict = {}
    try:
        line = await loadgen.line()
        if line != "READY":
            raise RuntimeError(f"load generator did not start: {line!r}")
        rec["before"] = _serve_snapshot(server.metrics)
        served.batches.clear()
        if trace_dir is not None:
            # host spans and device ops; no Python call tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles.__enter__()
        child.stdin.write(f"GO {server.port}\n")
        child.stdin.flush()
        while True:
            line = await loadgen.line()
            if not line or line == "END":
                break
            if line.startswith("START "):
                rec["t0"] = float(line.split()[1])
            say(f"loadgen: {line}")
        compiles.__exit__()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        rec["after"] = _serve_snapshot(server.metrics)
        if child.wait() != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
    finally:
        if compiles.on:
            compiles.__exit__()
        await server.close()
    rec["batches"] = list(served.batches)
    rec["compiles"] = compiles.n
    rec["compile_s"] = compiles.seconds
    rec["requests"] = [json.loads(x) for x in
                       loadgen.out.read_text().splitlines()]
    return rec


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------


def sample(records: list, traffic: Traffic, seed: int) -> list:
    """The answered requests compared with the reference: every planted
    query, then others drawn from the seed, ``CHECK_SAMPLE`` in all."""
    ok = [r for r in records if r["status"] == 200]
    rng = random.Random(seed)
    rng.shuffle(ok)
    planted = [r for r in ok if traffic.planted(r["n"])]
    rest = [r for r in ok if not traffic.planted(r["n"])]
    return (planted + rest)[:CHECK_SAMPLE]


def wrong(answer: dict, want: dict, query, theta: float, k: int) -> str:
    """Why a served answer is not the reference's ('' when it is)."""
    res = answer["result"]
    if res["theta"] != theta or res["query_len"] != len(query) or \
            res["degraded"]:
        return "theta, query_len or degraded differ"
    got = {m["doc_id"]: m for m in res["matches"]}
    if len(got) != len(res["matches"]):
        return "a document is reported twice"
    if set(got) != set(want):
        return (f"documents {sorted(set(got) ^ set(want))[:8]} differ "
                f"(served {len(got)}, reference {len(want)})")
    for d, m in got.items():
        hit, rows = want[d]
        if m["estimated_similarity"] != hit / k:
            return f"doc {d}: similarity {m['estimated_similarity']} " \
                   f"!= {hit}/{k}"
        if m["query_span"] != [0, len(query) - 1]:
            return f"doc {d}: query span {m['query_span']}"
        served = block_rows(m["blocks"])
        if not (served.shape == rows.shape and (served == rows).all()):
            return f"doc {d}: cells differ"
        if m["span"] != [int(rows[:, 0].min()), int(rows[:, 2].max())]:
            return f"doc {d}: span {m['span']}"
    return ""


def check(records: list, cell: Cell, docs, seed: int, seconds: float
          ) -> dict:
    """The numbers compared for ``correct``, each with its limit."""
    cfg = cell.config
    traffic = Traffic(cfg, cell.traffic, seed, seconds, docs)
    missing = sum(1 for r in records if r["status"] != 200)
    picked = sample(records, traffic, seed)
    t0 = time.monotonic()
    ref = traffic.reference
    n_wrong = 0
    for r in picked:
        q = traffic.query(r["n"])[0]
        why = wrong(json.loads(r["body"]), ref.answer(q), q, cfg["theta"],
                    cfg["k"])
        if why:
            n_wrong += 1
            say(f"answer {r['n']} wrong: {why}")
    say(f"reference: {len(picked)} answers in {time.monotonic() - t0!r} s")
    return {"answers_missing": {"value": missing, "limit": 0},
            "answers_wrong": {"value": n_wrong, "limit": 0},
            "answers_compared": {"value": len(picked), "limit": 1}}


def is_correct(checks: dict) -> bool:
    return (checks["answers_missing"]["value"] <=
            checks["answers_missing"]["limit"] and
            checks["answers_wrong"]["value"] <=
            checks["answers_wrong"]["limit"] and
            checks["answers_compared"]["value"] >=
            checks["answers_compared"]["limit"])


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def describe_device() -> dict:
    import jax
    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def set_up(cell: Cell, seconds: float) -> tuple:
    """Load the store (building it first if need be), upload its arena and
    warm up: (aligner, corpus documents, device arena)."""
    import jax

    from repro.api import Aligner
    from repro.compile_cache import configure_compile_cache
    from repro.core.device_plan import device_arena
    configure_compile_cache()
    t = [time.monotonic()]
    docs = make_corpus(cell.config)
    store = ensure_store(cell)
    t.append(time.monotonic())
    aligner = Aligner.load(str(store / "store"), mmap=True)
    t.append(time.monotonic())
    da = device_arena(aligner._index)
    jax.block_until_ready([da.khi, da.klo, da.ktag, da.offsets, da.win_rect])
    t.append(time.monotonic())
    say(f"arena: {da.nbytes} bytes, {da.n} slots, mode {da.mode}")
    warm_up(aligner, cell, docs, seconds)
    t.append(time.monotonic())
    say("set-up s: process start to set-up {!r}, corpus and store {!r}, "
        "load {!r}, upload {!r}, warm-up {!r}".format(
            t[0] - T_START, *(b - a for a, b in zip(t, t[1:]))))
    return aligner, docs, da


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             wrap=None, options: dict | None = None,
             loadgen: LoadGenChild | None = None) -> dict:
    """One run of ``cell``; the result line as a dict.  ``wrap``, when
    given, wraps the loaded aligner before it is served (tests plant
    faults there); ``options`` replaces the requests' query options (the
    lower-precision control pins the f32 sketch there); ``loadgen`` is
    the run's load generator, when the caller started it already."""
    import jax
    cfg = cell.config
    if loadgen is None:
        loadgen = LoadGenChild(cell, seed, seconds,
                               options or {"plan": "device"})
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        try:
            aligner, docs, da = set_up(cell, seconds)
            if wrap is not None:
                aligner = wrap(aligner)
            trace_dir = str(Path(tmp) / "trace") if trace else None
            rec = asyncio.run(serve_window(aligner, cell, loadgen,
                                           trace_dir))
        finally:
            loadgen.close()
        rec["trace"] = None
        if trace:
            from chipbench.trace_reduce import find_trace, reduce_trace
            path = find_trace(trace_dir)
            rec["trace"] = reduce_trace(path, SPAN) if path else None
    device = describe_device()
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    mode, k = da.mode, cfg["k"]
    del aligner, da
    gc.collect()

    rec.update(setup_s=rec["t0"] - T_START, seconds=seconds, k=k,
               giveup=rec["t0"] + seconds + GRACE_S,
               arena_mode=mode,
               peak=(peaks_for(cell.root, device["kind"])
                     if device["platform"] == "tpu" else None))
    checks = check(rec["requests"], cell, docs, seed, seconds)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(cell.root, m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": is_correct(checks),
              "attempted": len(rec["requests"]),
              "failed": checks["answers_missing"]["value"],
              "metrics": metrics, "device": device}
    if trace and rec["trace"] is not None:
        t = rec["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    say(f"compiles in the window: {rec['compiles']} "
        f"({rec['compile_s']!r} s)")
    for name, c in checks.items():
        bound = "at least" if name == "answers_compared" else "at most"
        say(f"check {name}: {c['value']} (limit: {bound} {c['limit']})")
    result["checks"] = checks
    return result


def peaks_for(root: Path, kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an
    error, never a default."""
    peaks = json.loads((root / "chipbench" / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no published peaks for device {kind!r} in "
                       "chipbench/peaks.json")
    return peaks[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    loadgen = LoadGenChild(cell, args.seed, args.seconds, {"plan": "device"})
    try:
        # the compile cache lives in the checkout, whatever the machine sets
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            ROOT / "chipbench" / ".cache" / "jax")
        import jax
        devs = jax.devices()
        if devs[0].platform != "tpu" or len(devs) < cell.chips:
            say(f"no run: the cell needs {cell.chips} TPU chip(s); JAX "
                f"reports {len(devs)} {devs[0].platform} device(s)")
            return 2
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          loadgen=loadgen)
    finally:
        loadgen.close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
