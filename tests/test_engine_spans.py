"""The engine's spans (``repro.core.spans``): the helper, the stage
seconds ``find_batch`` fills on every index kind and plan, the counters
at the same boundaries, the serve metrics that carry them to
``/metrics``, the spans on the profiler's host plane, and the names the
device programs keep in a trace."""

import asyncio
import glob
import importlib.util
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.api import Aligner, QueryOptions
from repro.core.device_plan import reset_transfer_stats, transfer_stats
from repro.core.spans import NAMES, add_seconds, span
from repro.serve import AlignServer, ServeMetrics
from repro.serve.client import AsyncAlignClient

ROOT = Path(__file__).resolve().parents[1]
PARENTS = ("sketch", "probe", "sweep")


def _docs():
    # three small-vocabulary documents give (query, text) groups of more
    # than 32 windows (swept on the host), the rest small groups (swept
    # by the grouped or device sweep)
    rng = np.random.default_rng(7)
    docs = [rng.integers(0, 50, size=300) for _ in range(3)]
    docs += [rng.integers(0, 5000, size=100) for _ in range(4)]
    docs.append(docs[4].copy())
    qs = [docs[0][:200], docs[3][10:60], docs[4][:80],
          rng.integers(0, 5000, size=30)]
    return docs, qs


@pytest.fixture(scope="module")
def corpus():
    return _docs()


def _aligner(kind, docs, tmp_path):
    if kind == "live":
        store = str(tmp_path / "idx")
        Aligner.build(docs, similarity="multiset", seed=3, k=8,
                      pipeline="columnar", store=store)
        return Aligner.load(store, live=True)
    al = Aligner.build(docs, similarity="multiset", seed=3, k=8,
                       shards=2 if kind == "sharded" else 1)
    return al if kind == "builder" else al.freeze()


# --------------------------------------------------------------------------
# the helper
# --------------------------------------------------------------------------

def test_span_accumulates_and_nests():
    times = {}
    for _ in range(2):
        with span(times, "sweep"):
            with span(times, "sweep.large"):
                time.sleep(0.002)
            time.sleep(0.001)
    assert set(times) == {"sweep", "sweep.large"}
    assert times["sweep.large"] >= 0.004
    assert times["sweep"] >= times["sweep.large"] + 0.002


def test_span_without_a_dict_writes_nothing():
    with span(None, "probe"):
        pass
    add_seconds(None, "probe", 1.0)
    times = {"probe": 1.0}
    add_seconds(times, "probe", 0.5)
    assert times == {"probe": 1.5}


def test_span_times_a_body_that_raises():
    times = {}
    with pytest.raises(ValueError):
        with span(times, "sweep"):
            raise ValueError("boom")
    assert times["sweep"] >= 0.0


def test_importing_the_helper_and_the_serve_path_imports_no_jax():
    code = ("import sys; import repro.core.spans, repro.core.query, "
            "repro.serve; assert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_names_are_unique_and_children_have_parents():
    assert len(set(NAMES)) == len(NAMES)
    for name in NAMES:
        if "." in name and not name.startswith("serve."):
            assert name.rsplit(".", 1)[0] in NAMES


# --------------------------------------------------------------------------
# find_batch fills the spans: every index kind, both plans
# --------------------------------------------------------------------------

@pytest.mark.parametrize("plan", ["cpu", "device"])
@pytest.mark.parametrize("kind", ["frozen", "builder", "live", "sharded"])
def test_find_batch_spans_nest_inside_their_parents(tmp_path, corpus, kind,
                                                    plan):
    docs, qs = corpus
    al = _aligner(kind, docs, tmp_path)
    opts = QueryOptions(plan=plan)
    want = al.find_batch(qs, 0.5, options=opts)          # and warm
    times = {}
    t0 = time.perf_counter()
    got = al.find_batch(qs, 0.5, options=opts, stage_times=times)
    wall = time.perf_counter() - t0
    assert [r.to_dict() for r in got] == [r.to_dict() for r in want]
    assert set(times) <= set(NAMES)
    for name in PARENTS + ("results", "sweep.group", "sweep.large",
                           "sweep.emit"):
        assert name in times, name
    for name, seconds in times.items():
        if "." in name:
            assert seconds <= times[name.rsplit(".", 1)[0]], name
    kids = [n for n in times if n.count(".") == 1 and
            n.startswith("sweep.")]
    assert sum(times[n] for n in kids) <= times["sweep"]
    # the parents keep the old stage boundaries: back to back, so with
    # the results they take all of the call but its option and token
    # handling
    spent = sum(times[n] for n in PARENTS) + times["results"]
    assert spent <= wall
    assert wall - spent < 0.05 + 0.1 * wall
    # under the device plan every frozen level (a frozen store, a live
    # store's base, each shard) is probed on its resident arena and
    # grouped over text runs; the sharded fan-out's pool threads time no
    # span, so its probe calls go untimed
    resident = plan == "device" and kind != "builder"
    assert ("probe.device" in times) == (resident and kind != "sharded")
    assert ("probe.gather" in times) == resident
    assert ("sweep.large.read" in times) == resident
    assert ("sweep.device" in times) == (plan == "device")


def test_device_counters_agree_across_the_fused_and_host_probe_paths(
        tmp_path, corpus):
    docs, qs = corpus
    got = {}
    for kind in ("frozen", "builder"):
        al = _aligner(kind, docs, tmp_path)
        al.find_batch(qs, 0.5, options=QueryOptions(plan="device"))
        reset_transfer_stats()
        al.find_batch(qs, 0.5, options=QueryOptions(plan="device"))
        st = transfer_stats()
        got[kind] = {key: st[key] for key in (
            "probe_windows", "groups_kept", "host_large_groups",
            "host_large_windows", "sweep_launches")}
    assert got["frozen"] == got["builder"]
    st = got["frozen"]
    assert st["probe_windows"] >= st["host_large_windows"] > \
        32 * st["host_large_groups"] > 0
    assert st["groups_kept"] > st["host_large_groups"]
    assert st["sweep_launches"] >= 1


# --------------------------------------------------------------------------
# the serve metrics carry every span
# --------------------------------------------------------------------------

def test_stage_seconds_start_with_every_span_at_zero():
    m = ServeMetrics()
    assert set(m.stage_seconds) == set(NAMES) | {"queue_wait"}
    assert all(v == 0.0 for v in m.stage_seconds.values())


def test_observe_batch_carries_every_key_and_observe_span_adds():
    m = ServeMetrics()
    m.observe_batch(2, [0.25, 0.5], {"sketch": 1.0, "sweep.large": 2.0,
                                     "sweep.large.read": 0.5,
                                     "new.key": 3.0})
    m.observe_batch(1, [0.25], {"sweep.large": 1.0})
    m.observe_span("serve.parse", 0.125)
    m.observe_span("serve.parse", 0.125)
    st = m.snapshot()["stage_seconds"]
    assert st["sketch"] == 1.0
    assert st["sweep.large"] == 3.0
    assert st["sweep.large.read"] == 0.5
    assert st["new.key"] == 3.0
    assert st["queue_wait"] == 1.0
    assert st["serve.parse"] == 0.25
    assert st["probe"] == 0.0


def test_metrics_endpoint_reports_the_sub_stage_seconds(corpus):
    docs, qs = corpus
    al = Aligner.build(docs, similarity="multiset", seed=3, k=8).freeze()

    async def main():
        async with AlignServer(al, max_linger_us=100.0) as srv:
            client = await AsyncAlignClient.connect("127.0.0.1", srv.port)
            status, _ = await client.query([int(t) for t in qs[0]], 0.5,
                                           options={"plan": "device"})
            snap = await client.metrics()
            await client.close()
            return status, snap

    status, snap = asyncio.run(main())
    assert status == 200
    st = snap["stage_seconds"]
    for name in ("sketch", "probe", "probe.device", "probe.gather",
                 "sweep", "sweep.group", "sweep.large", "sweep.emit",
                 "results", "serve.parse", "serve.respond"):
        assert st[name] > 0, name
    assert st["probe.device"] + st["probe.gather"] <= st["probe"]


# --------------------------------------------------------------------------
# the profiler's trace
# --------------------------------------------------------------------------

def test_spans_land_nested_on_the_profilers_host_plane(tmp_path, corpus):
    import jax
    from jax.profiler import ProfileData
    docs, qs = corpus
    al = _aligner("frozen", docs, tmp_path)
    opts = QueryOptions(plan="device")
    al.find_batch(qs, 0.5, options=opts)                  # compile first
    with jax.profiler.trace(str(tmp_path / "trace")):
        al.find_batch(qs, 0.5, options=opts, stage_times={})
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in NAMES:
                    found.setdefault(e.name, []).append(
                        (plane.name, line.name, e.start_ns,
                         e.start_ns + e.duration_ns))
    assert set(found) >= {"sketch", "probe", "probe.device",
                          "probe.gather", "sweep", "sweep.group",
                          "sweep.device", "sweep.large", "sweep.emit",
                          "results"}
    assert "sweep.large.read" not in found        # seconds only
    assert all(p.startswith("/host:") for evs in found.values()
               for p, *_ in evs)

    def inside(child, parent):
        (pp, pl, ps, pe), = found[parent]
        return all((cp, cl) == (pp, pl) and ps <= cs and ce <= pe
                   for cp, cl, cs, ce in found[child])

    for child in ("probe.device", "probe.gather"):
        assert inside(child, "probe")
    for child in ("sweep.group", "sweep.device", "sweep.large",
                  "sweep.emit"):
        assert inside(child, "sweep")


def test_device_programs_keep_their_trace_names():
    # the trace names a program by its HLO module; the benchmark's
    # roofline reader looks the probe up by that name
    import jax
    import jax.numpy as jnp

    from repro.core.device_plan import _probe_jit_factory
    from repro.kernels.sweep_grid import sweep_grid
    u32 = jax.ShapeDtypeStruct((64,), jnp.uint32)
    q32 = jax.ShapeDtypeStruct((16,), jnp.uint32)
    probe = _probe_jit_factory().lower(
        u32, u32, u32, jax.ShapeDtypeStruct((65,), jnp.int32),
        q32, q32, q32, jax.ShapeDtypeStruct((16,), jnp.bool_))
    sweep = sweep_grid.lower(jax.ShapeDtypeStruct((5, 8, 4), jnp.int32),
                             jax.ShapeDtypeStruct((5,), jnp.int32), m=3)

    def module(lowered):
        first = lowered.compile().as_text().splitlines()[0]
        return first.split()[1].rstrip(",")

    assert module(probe) == "jit_probe"
    assert module(sweep) == "jit_sweep_grid"
    spec = importlib.util.spec_from_file_location(
        "probe_roofline", ROOT / "chipbench" / "metrics" / "probe_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    sys.path.insert(0, str(ROOT))
    try:
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(str(ROOT))
    assert reader.PROGRAM == module(probe)
