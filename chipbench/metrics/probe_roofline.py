"""The arena probe's share of its roofline, in percent: the least time of
the window's probe calls (``chipbench/roofline/probe.py``, bound by HBM
bandwidth) over the device time of the probe program (``jit_probe``) in
the trace.  Nothing without a trace, a peak table entry, or that program
in the trace."""

from chipbench.roofline.probe import least_seconds

PROGRAM = "jit_probe"


def read(rec: dict) -> float | None:
    t, peak = rec.get("trace"), rec.get("peak")
    if t is None or peak is None or not t["program_s"].get(PROGRAM):
        return None
    least = least_seconds([b * rec["k"] for b in rec["batches"]],
                          rec["arena_mode"], peak)
    return 100.0 * least / t["program_s"][PROGRAM]
