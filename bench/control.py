"""The float32 control of the check that decides ``correct``.

For each seed, each cell's query answers computed by the reference in
float32 (``reference.run(..., numpy.float32)``), the nearest precision
below the configuration's float64, take the program's place and are
judged by ``compare.judge`` against the float64 reference, at the cell's
own size. The tables are fixed by the configuration and a seed only
places partitions, which the reference does not see, so every seed reads
alike. Prints one JSON line per cell and seed with the numbers the check
compares; ``bench/limits.json`` is set from these readings and the
program's (PERF.md gives both).

    python3 bench/control.py --seeds 1 2 3 [--cells sf1-power ...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cells", nargs="*")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import compare, datagen, harness, reference

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = args.cells or [w["name"] for w in json.load(f)["workloads"]]
    cells = [harness.load_cell(n) for n in names]
    limits = compare.load_limits()
    for seed in args.seeds:
        by_config: dict = {}
        for cell in cells:
            key = json.dumps(cell.config, sort_keys=True)
            if key not in by_config:
                by_config[key] = datagen.tables_for(cell.config)
            tables = by_config[key]
            t0 = time.perf_counter()
            want = {q: reference.run(q, tables) for q in cell.queries}
            t1 = time.perf_counter()
            got = [(q, reference.run(q, tables, np.float32).cols)
                   for q in cell.queries]
            v = compare.judge(got, want, 0, limits)
            print(json.dumps({"cell": cell.name, "seed": seed,
                              "correct": v.correct, "compared": v.numbers(),
                              "first_wrong": v.first_wrong,
                              "reference_s": t1 - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
