#!/usr/bin/env python3
"""The device's idle time, put down to the program's own spans.

The query engine writes its stages to the profiler's trace as host spans
(``repro.core.spans``: ``probe``, ``probe.device``, ``sweep.large``, ...),
on the same clock as the device's operations.  From a run's
``.xplane.pb`` this labels every interval in which the device runs no
operation by the innermost program span open on any host thread: the
deepest span (nesting on its own thread), the latest opened among equals.
Idle time with no program span open is *unattributed*: the host was
outside the engine and the front end (the batcher's linger, the event
loop's socket work, the load generator's turn).

It also gives each program's device time inside each span (the probe
program ``jit_probe`` should lie inside ``probe.device``) and the share
of each parent span its direct children cover (``sweep`` by
``sweep.group``, ``sweep.device``, ``sweep.large``, ``sweep.emit``).

Planes are read as :func:`chipbench.trace_reduce.reduce_planes` reads
them: ``/device:TPU:<n>`` planes with ``XLA Ops`` and ``XLA Modules``
lines, ``/host:`` planes with one line per thread.

    python chipbench/trace_spans.py --workload <cell> --seed <n> \\
        --seconds <s>

runs the cell as ``chipbench/run.py --trace 1`` does and prints, after
its result line, one more JSON line: this reduction of the run's trace
under ``trace_spans``, and ``queries_per_s`` as the traced window itself
read it.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.trace_reduce import (_DEVICE, _overlap,  # noqa: E402
                                    program_name, union)


def span_names() -> tuple:
    """The program's span names; none from a program that has no
    ``repro.core.spans``."""
    try:
        from repro.core.spans import NAMES
    except ImportError:
        return ()
    return NAMES


def _nested(events) -> list[tuple]:
    """(start, end, depth, name) of one thread's spans; depth counts the
    spans of that thread still open at the start."""
    out, open_ends = [], []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        open_ends = [x for x in open_ends if x > s]
        out.append((s, e, len(open_ends), name))
        open_ends.append(e)
    return out


def _labelled(spans: list[tuple]) -> list[tuple]:
    """Consecutive (start, end, name) pieces between span edges, each
    named by the innermost span open over it; pieces with no span open
    are left out."""
    edges = sorted({t for s, e, _, _ in spans for t in (s, e)})
    opens = sorted(spans)
    out, active, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(opens) and opens[i][0] <= a:
            active.append(opens[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            inner = max(active, key=lambda sp: (sp[2], sp[0]))
            out.append((a, b, inner[3]))
    return out


def _idle_by_label(idle: list[tuple], pieces: list[tuple]) -> dict:
    """Seconds (ns here) of the idle intervals under each labelled piece;
    both lists sorted and each free of overlaps."""
    got: dict = {}
    j = 0
    for s, e in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
            if ov > 0:
                got[pieces[k][2]] = got.get(pieces[k][2], 0.0) + ov
            k += 1
    return got


def reduce_span_planes(planes, names) -> dict | None:
    """The reduction of :func:`reduce_spans` over ``(name, lines)`` planes
    (as :func:`chipbench.trace_reduce.reduce_planes` takes them); ``None``
    when no device plane ran anything."""
    names = set(names)
    starts, ends, spans, devices = [], [], [], []
    for pname, lines in planes:
        for _, events in lines:
            for _, s, d in events:
                starts.append(s)
                ends.append(s + d)
        if pname.startswith("/host:"):
            for _, events in lines:
                spans += _nested((s, s + d, n) for n, s, d in events
                                 if n in names)
        if _DEVICE.match(pname):
            devices.append(dict(lines))
    devices = [d for d in devices if d.get("XLA Ops")]
    if not devices or not starts:
        return None
    window = (min(starts), max(ends))
    pieces = _labelled(spans)
    by_name = {n: union((s, e) for s, e, _, m in spans if m == n)
               for n in sorted({sp[3] for sp in spans})}

    idle_ns, idle_by, program_ns, program_in = 0.0, {}, {}, {}
    for lines in devices:
        busy = union((s, s + d) for _, s, d in lines["XLA Ops"])
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        idle_ns += sum(e - s for s, e in idle)
        for name, ns in _idle_by_label(idle, pieces).items():
            idle_by[name] = idle_by.get(name, 0.0) + ns
        for n, s, d in lines.get("XLA Modules", ()):
            prog = program_name(n)
            program_ns[prog] = program_ns.get(prog, 0.0) + d
            inside = program_in.setdefault(prog, {})
            for name, ivs in by_name.items():
                inside[name] = inside.get(name, 0.0) + \
                    _overlap((s, s + d), ivs)
    n_dev = len(devices)
    attributed = sum(idle_by.values())
    cover = {}
    for parent, ivs in by_name.items():
        kids = union(iv for name, kid in by_name.items()
                     if name.startswith(parent + ".") and
                     "." not in name[len(parent) + 1:] for iv in kid)
        total = sum(e - s for s, e in ivs)
        if kids and total:
            cover[parent] = sum(_overlap(iv, kids) for iv in ivs) / total
    return {
        "idle_s": idle_ns / n_dev / 1e9,
        "idle_by_span": {k: v / n_dev / 1e9 for k, v in
                         sorted(idle_by.items(), key=lambda x: -x[1])},
        "idle_unattributed_s": (idle_ns - attributed) / n_dev / 1e9,
        "idle_unattributed_share": ((idle_ns - attributed) / idle_ns
                                    if idle_ns else 0.0),
        "span_s": {k: sum(e - s for s, e in v) / 1e9
                   for k, v in by_name.items()},
        "child_cover": cover,
        "program_in_span": {
            prog: {name: ns / program_ns[prog]
                   for name, ns in inside.items() if ns > 0}
            for prog, inside in program_in.items() if program_ns[prog]},
    }


def reduce_spans(path: str, names=None) -> dict | None:
    """Device-idle seconds per innermost program span, the unattributed
    idle seconds and their share of all idle time, each span's seconds,
    each parent span's share covered by its children, and each program's
    share of device time inside each span; ``None`` when the trace holds
    no device operation.  ``names`` defaults to the program's span
    names."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events])
                        for ln in p.lines])
              for p in data.planes]
    return reduce_span_planes(planes, span_names() if names is None
                              else names)


def main(argv=None) -> int:
    """One traced run of a cell (``chipbench/run.py`` with ``--trace
    1``), then this reduction of its trace."""
    from chipbench import run, trace_reduce
    argv = list(sys.argv[1:] if argv is None else argv)
    kept: dict = {}
    reduce_trace, serve_window = trace_reduce.reduce_trace, run.serve_window

    def reduce_both(path, span=run.SPAN):
        kept["trace_spans"] = reduce_spans(path)
        return reduce_trace(path, span)

    async def serve_kept(*args, **kw):
        kept["rec"] = rec = await serve_window(*args, **kw)
        return rec

    trace_reduce.reduce_trace, run.serve_window = reduce_both, serve_kept
    try:
        rc = run.main(argv + ["--trace", "1"])
    finally:
        trace_reduce.reduce_trace, run.serve_window = (reduce_trace,
                                                       serve_window)
    rec = kept.pop("rec", None)
    if rec is not None:
        kept["queries_per_s"] = run.load_reader(ROOT, "queries_per_s")(rec)
    print(json.dumps(kept), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
