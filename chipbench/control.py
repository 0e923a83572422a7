#!/usr/bin/env python3
"""The controls of ``correct``: comparisons that must come out wrong.

    python chipbench/control.py --workload <cell> --seeds 1 2 3 \
        [--kind step|f32] [--seconds S]

* ``step`` (every cell): the reference put in the program's place, one
  step below the configuration's guarantee: a subsequence counts as a
  result when it shares ceil(k * theta) - 1 coordinates with the query
  instead of ceil(k * theta).  Its answers, on the queries a run of the
  seed would compare (every planted query first, ``CHECK_SAMPLE`` in
  all, from the first ``POOL`` of the traffic), are held against the
  reference exactly as a run holds the program's.
* ``f32`` (tf-idf cells): the program with its own lower-precision path
  switched on: the window served with ``sketch_backend="pallas"``, the
  f32 on-device ICWS sketch, in place of the exact float64 one.  Needs
  the chip, like a run.

Prints one line per seed with ``answers_wrong`` and the compared count.
The benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.reference import Reference, load_scheme  # noqa: E402
from chipbench.workload import POOL, Traffic, make_corpus  # noqa: E402


def as_served(answer: dict, query, theta: float, k: int) -> dict:
    """A reference answer in the served JSON form (one block per run)."""
    matches = []
    for d, (hit, rows) in sorted(answer.items()):
        matches.append({
            "doc_id": d,
            "span": [int(rows[:, 0].min()), int(rows[:, 2].max())],
            "query_span": [0, len(query) - 1],
            "estimated_similarity": hit / k,
            "blocks": [[int(i), int(i), int(lo), int(hi)]
                       for i, lo, hi in rows]})
    return {"result": {"matches": matches, "theta": theta,
                       "query_len": len(query), "degraded": False,
                       "failed_shards": []}}


def step_control(cell, seed: int, seconds: float) -> dict:
    """``answers_wrong`` of the reference one coordinate short of the
    guarantee, on the queries a run of ``seed`` compares."""
    from chipbench.run import CHECK_SAMPLE, wrong
    cfg = cell.config
    docs = make_corpus(cfg)
    traffic = Traffic(cfg, cell.traffic, seed, seconds, docs)
    scheme = load_scheme(cfg, docs)
    ref = Reference(docs, scheme, cfg["theta"])
    control = Reference(docs, scheme, cfg["theta"], need=ref.need - 1)
    n = POOL if traffic.due is None else len(traffic.due)
    queries = [traffic.query(i) for i in range(n)]
    picked = ([q for q, p in queries if p is not None] +
              [q for q, p in queries if p is None])[:CHECK_SAMPLE]
    bad = sum(1 for q in picked
              if wrong(as_served(control.answer(q), q, cfg["theta"],
                                 cfg["k"]),
                       ref.answer(q), q, cfg["theta"], cfg["k"]))
    return {"answers_wrong": bad, "answers_compared": len(picked),
            "need": ref.need, "control_need": control.need,
            "theta_needs": math.ceil(cfg["k"] * cfg["theta"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--kind", choices=("step", "f32"), default="step")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    from chipbench import run
    cell = run.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        if args.kind == "step":
            out = step_control(cell, seed, args.seconds)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
                ROOT / "chipbench" / ".cache" / "jax")
            res = run.run_cell(cell, seed, args.seconds, False,
                               options={"plan": "device",
                                        "sketch_backend": "pallas"})
            out = {k: v["value"] for k, v in res["checks"].items()}
        print(json.dumps({"workload": args.workload, "kind": args.kind,
                          "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
