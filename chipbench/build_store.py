#!/usr/bin/env python3
"""Build a configuration's store, in a process of its own.

    python chipbench/build_store.py CONFIG.json STORE_DIR

The run process starts this when its checkout lacks the store, so the
process that serves the window holds nothing of the build: every run of
a cell serves from the same state, the first as the later ones.  JAX
stays on the CPU here; the chip belongs to the run process.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.workload import make_corpus  # noqa: E402


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    cfg = json.loads(Path(argv[0]).read_text())
    from repro.api import Aligner
    docs = make_corpus(cfg)
    t0 = time.monotonic()
    Aligner.build(docs, similarity=cfg["similarity"], k=cfg["k"],
                  seed=cfg["hash_seed"], method=cfg["method"],
                  tf=cfg.get("tf", "raw"), idf=cfg.get("idf"),
                  family=cfg.get("family", "universal"),
                  pipeline="columnar", store=argv[1])
    print(f"built store: {sum(len(d) for d in docs)} tokens in "
          f"{time.monotonic() - t0!r} s", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
