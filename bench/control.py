"""Reads a cell's compared numbers for the program and for its control.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 \
        [--data-seed <n>]

For each seed, in this one process, it makes a whole run of the cell (its
window shortened to ``--seconds``) with the control in the program's place:
the reference itself, scoring in bfloat16, the precision below the float32
the configuration states.  The control's number goes through the run's own
checks, so a sound limit makes ``correct`` false; the program's own reading
of the same number is printed beside it.  ``--data-seed`` draws the
configuration's training set from another seed.  The benchmark's own runs
never run the control.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--data-seed", type=int, default=None)
    args = ap.parse_args(argv)
    if args.data_seed is not None:
        load = harness.load

        def load_with_data_seed(path):
            d = load(path)
            if path.parent.name == "configs":
                d["data"]["data_seed"] = args.data_seed
            return d

        harness.load = load_with_data_seed
    for seed in args.seeds:
        res = harness.execute(
            ["--workload", args.workload, "--seed", str(seed),
             "--seconds", str(args.seconds), "--trace", "0"], control=True)
        print(json.dumps({"seed": seed, "data_seed": args.data_seed,
                          "correct": res["correct"],
                          "metrics": res["metrics"], "notes": res["notes"],
                          "program": res["program"],
                          "control": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
