"""Benchmark harness entry point: one module per paper table/figure plus
the roofline reporter.  ``python -m benchmarks.run [--full] [--only NAME]``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from . import (bench_active_opt, bench_build, bench_live, bench_query,
               bench_serve, bench_sketch_kernels, bench_vs_allalign,
               bench_weights, roofline)

SUITES = {
    "active_opt": bench_active_opt.run,      # paper Fig. 5
    "weights": bench_weights.run,            # paper Fig. 6
    "vs_allalign": bench_vs_allalign.run,    # paper Fig. 7
    "query": bench_query.run,                # paper §6 query study
    "build": bench_build.run,                # §6 construction study
    "live": bench_live.run,                  # incremental-serve study
    "serve": bench_serve.run,                # serving front-end study
    "sketch_kernels": bench_sketch_kernels.run,
    "roofline": roofline.run,                # EXPERIMENTS.md §Roofline
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow); default is scaled-down")
    ap.add_argument("--only", default=None, choices=list(SUITES))
    args = ap.parse_args(argv)
    from repro.compile_cache import configure_compile_cache
    configure_compile_cache()

    failures = []
    all_claims = {}
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"\n######## {name} ########", flush=True)
        try:
            rec = fn(quick=not args.full)
            claims = rec.get("claims", {})
            all_claims[name] = claims
            for cname, ok in claims.items():
                mark = "PASS" if ok else "FAIL"
                print(f"  [{mark}] {cname}")
                if not ok:
                    failures.append(f"{name}:{cname}")
        except Exception as e:  # pragma: no cover
            failures.append(f"{name}:exception:{e}")
            import traceback
            traceback.print_exc()
        print(f"  ({time.time() - t0:.1f}s)")

    print("\n==== paper-claim summary ====")
    print(json.dumps(all_claims, indent=1))
    if failures:
        print("FAILURES:", failures)
        return 1
    print("all benchmark claims hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
