"""Named wall-clock spans of the query engine, on the profiler's clock.

``with span(times, name):`` adds the wall seconds of its body to
``times[name]`` (when ``times`` is a dict) and, when JAX is already
imported, opens ``jax.profiler.TraceAnnotation(name)`` around the body,
so the span lands on the host plane of a profiler trace on the same
clock as the device's operations.  Spans nested on one thread nest in
the trace too.  Importing this module never imports JAX, and with no
profiler session an annotation costs about a microsecond.

The span names are the ``stage_times`` keys the engine fills (and
``ServeMetrics.stage_seconds`` accumulates), letter for letter:

================== ======================================================
``sketch``         sketching the batch
``probe``          encoding the probe keys, the probe, the window gather
``probe.device``   the resident-arena probe call through its read-back
``probe.gather``   the window gather: on a resident level (the device
                   plan), the text runs of the probe's extents, read
                   from the run table
``sweep``          grouping, every sweep, and building the alignments
``sweep.group``    the (query, text) grouping and the >= m prefilter;
                   on a resident level over text runs, then the kept
                   groups' rows
``sweep.device``   per size bucket: the device gather and sweep through
                   the read-back of the coverage grids
``sweep.large``    the host sweep of the groups the grouped sweep leaves
``sweep.large.read`` (seconds only) the mmap row reads of those groups
``sweep.large.filter`` (seconds only) the test that rejects those groups
                   that cannot hold a cell covered >= m times
``sweep.emit``     run extraction and building the ``Alignment``s
``results``        building the ``QueryResult``s (``Aligner.find_batch``)
``serve.parse``    the server's request parse and tokenisation
``serve.respond``  the server's response ``to_dict`` and JSON encoding
================== ======================================================

A ``sweep.*`` or ``probe.*`` span lies inside its parent; the parents'
boundaries are those of the three stages the engine always timed.
"""

from __future__ import annotations

import sys
import time
from contextlib import contextmanager

__all__ = ["NAMES", "add_seconds", "span"]

NAMES = ("sketch", "probe", "probe.device", "probe.gather",
         "sweep", "sweep.group", "sweep.device", "sweep.large",
         "sweep.large.read", "sweep.large.filter", "sweep.emit", "results",
         "serve.parse", "serve.respond")


def add_seconds(times: dict | None, name: str, seconds: float) -> None:
    """``times[name] += seconds`` (nothing when ``times`` is ``None``)."""
    if times is not None:
        times[name] = times.get(name, 0.0) + seconds


@contextmanager
def span(times: dict | None, name: str):
    """Time the body into ``times[name]`` and annotate it as ``name`` on
    the profiler's trace (when JAX is imported)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    ann = profiler.TraceAnnotation(name) if profiler is not None else None
    if ann is not None:
        ann.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        add_seconds(times, name, time.perf_counter() - t0)
        if ann is not None:
            ann.__exit__(None, None, None)
