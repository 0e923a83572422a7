"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: the device's busy time and idle share, device time per program
and per operation, and the idle gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData``.  A device plane is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
run and its ``XLA Modules`` line one per program run.  Host spans are the
events of the ``/host:CPU`` plane.  All timestamps share the trace's
clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def find_trace(log_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    got = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                    recursive=True)
    return max(got, key=os.path.getmtime) if got else None


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a: tuple, spans: list[tuple]) -> float:
    return sum(max(0.0, min(a[1], e) - max(a[0], s)) for s, e in spans)


def _owner(modules: list, starts: list, t: float) -> str:
    """The program whose run holds time t ('?' if none does)."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][2] if i >= 0 and t <= modules[i][1] else "?"


def program_name(name: str) -> str:
    """A program's name without the run-id suffix the trace adds."""
    return _SUFFIX.sub("", name).strip()


def reduce_planes(planes, span: str) -> dict | None:
    """The reduction of :func:`reduce_trace`, over ``(name, lines)``
    planes whose lines are ``(name, [(event name, start_ns,
    duration_ns), ...])``; ``None`` when no device plane ran anything."""
    starts, ends = [], []
    spans: list[tuple] = []
    devices = []
    for pname, lines in planes:
        for _, events in lines:
            for _, s, d in events:
                starts.append(s)
                ends.append(s + d)
        if pname.startswith("/host:"):
            spans += [(s, s + d) for _, evs in lines for n, s, d in evs
                      if n == span]
        if _DEVICE.match(pname):
            devices.append(dict(lines))
    devices = [d for d in devices if d.get("XLA Ops")]
    if not devices or not starts:
        return None
    window = (min(starts), max(ends))
    window_ns = window[1] - window[0]
    spans = union(spans)
    busy, programs, ops, gaps = [], {}, {}, []
    for lines in devices:
        busy_iv = union((s, s + d) for _, s, d in lines["XLA Ops"])
        busy.append(sum(e - s for s, e in busy_iv))
        modules = sorted((s, s + d, program_name(n))
                         for n, s, d in lines.get("XLA Modules", ()))
        for s, e, key in modules:
            programs[key] = programs.get(key, 0.0) + (e - s)
        starts_m = [m[0] for m in modules]
        for n, s, d in lines["XLA Ops"]:
            key = f"{_owner(modules, starts_m, s)}/{n.split(' = ')[0]}"
            ops[key] = ops.get(key, 0.0) + d
        edges = [window[0]] + [x for iv in busy_iv for x in iv] + [window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                inside = _overlap((s, e), spans)
                label = (f"host in {span}" if inside * 2 >= e - s
                         else f"host outside {span}")
                gaps.append((label, (e - s) / 1e9))
    n = len(devices)
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": window_ns / 1e9,
        "idle_share": 1.0 - sum(busy) / n / window_ns,
        "program_s": {k: v / n / 1e9 for k, v in programs.items()},
        "device_ops": sorted(([k, v / n / 1e9] for k, v in ops.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(([k, v] for k, v in gaps),
                            key=lambda x: -x[1])[:10],
        "span_s": sum(e - s for s, e in spans) / 1e9,
    }


def reduce_trace(path: str, span: str = "chipbench.find_batch"
                 ) -> dict | None:
    """Busy seconds (averaged over the device planes), the traced window,
    the idle share, device seconds per program, the ten operations that
    took most device time, and the ten longest idle gaps, each labelled by
    whether the host span ``span`` covered most of it.  ``None`` when the
    trace holds no device operation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events])
                        for ln in p.lines])
              for p in data.planes]
    return reduce_planes(planes, span)
