"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only build,phases] [--list]

Prints ``name,us_per_call,derived`` CSV (one row per measured
configuration).  Module -> paper-artifact map lives in DESIGN.md §6.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

from repro.compile_cache import use_compile_cache

BENCHES = [
    "build",            # Fig. 1 / Fig. 5 build times
    "qps_recall",       # Fig. 5 QPS-recall curves
    "fanout",           # Fig. 3 / Supp. Figs. 8-9
    "phases",           # Fig. 4 phase breakdown
    "partitioning",     # Table 2 / Supp. Fig. 7
    "leaf_methods",     # Fig. 10 / Table 3
    "leaf_k",           # Fig. 11
    "leaf_opts",        # Fig. 12 / Supp. A.4
    "hashprune_params",  # Fig. 13 / Table 5
    "knn_graph",        # Fig. 6 downstream task
    "kernels",          # Pallas kernels vs ref oracles
    "distributed",      # beyond-paper: SPMD build path
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated subset of benches")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(BENCHES))
        return 0
    selected = [b for b in args.only.split(",") if b] or BENCHES
    use_compile_cache()

    print("name,us_per_call,derived")
    n_fail = 0
    for bench in selected:
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(f"benchmarks.bench_{bench}")
            rows = mod.run()
        except Exception as e:
            n_fail += 1
            print(f"{bench},ERROR,\"{type(e).__name__}: {e}\"")
            traceback.print_exc(file=sys.stderr)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},\"{derived}\"")
        print(f"# {bench} done in {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
