"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout of the repository, on a machine that
holds the chips the cell asks for. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device`` and, traced, ``breakdown``; last in it,
``compared`` gives each number the check compared with its limit, and so
do the last lines of standard error. An earlier ``bench-setup`` line
splits the set-up time into its parts. Without a device of the peaks
table (``bench/peaks.json``), with fewer chips than the cell asks for, or
outside a checkout, it exits non-zero and prints no result.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, so only the first run of a cell there compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402 — the clock above starts the set-up time
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg: str, code: int = 1) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"no engine at {ROOT}/src/repro: run from a checkout")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    with open(os.path.join(harness.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX found {devices[0].platform} ({kind})")
    if kind not in peaks:
        return fail(f"device {kind!r} is not in bench/peaks.json")
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, "
                    f"JAX found {len(devices)}")

    def log(line: str) -> None:
        print(line, flush=True)

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS, log)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
