"""Read a cell's control: the number each check compares, as the
lower-precision control put in the program's place gives it, at the
cell's own size.

    python3 benchmark/control.py --workload g500-s21.converge --seeds 1 2 3

Prints one JSON line per seed. The limits in ``benchmark/configs`` lie
below the smallest of these readings (see PERF.md). Benchmark runs do
not run the control.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from benchmark import run

    spec = run.cell_spec(args.workload)
    runner = importlib.import_module(
        "benchmark.runners." + spec["traffic"]["runner"])
    for seed in args.seeds:
        readings = runner.control(spec["config"], seed)
        limits = spec["config"]["checks"]
        print(json.dumps({"seed": seed, **readings,
                          "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
