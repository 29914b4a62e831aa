"""Benchmark entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig8,table2]

Prints ``name,us_per_call,derived`` CSV rows (stdout), one per measurement.
"""

from __future__ import annotations

import argparse
import sys
import traceback

MODULES = (
    "table1_datasets",
    "fig8_np",
    "fig9_nap",
    "fig10_11_scalability",
    "fig12_cost_models",
    "fig13_scheduling",
    "fig_superstep",
    "fig_infer",
    "fig_ensemble",
    "fig_faults",
    "table2_quadcore",
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substring filter")
    args = ap.parse_args()
    import importlib

    from repro import compile_cache
    compile_cache.enable()

    from benchmarks.common import emit
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if args.only and not any(s in mod_name
                                 for s in args.only.split(",")):
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            emit(mod.run())
        except Exception:
            failed.append(mod_name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
