"""Smoke run of the pushdown engine's compute layer on one TPU chip.

Drives TPC-H through the engine's own entry points (``engine.run_query``
and ``runtime.run_stream``) with the tensorized residual
(``EngineConfig(residual="tensor")``), and checks every answer against the
numpy interpreter oracle (``EngineConfig()``) with ``engine.results_equal``:

- ``sf1``: TPC-H SF1 (generator ``sf=100``, 6M lineitem rows), all 15
  queries under ``adaptive`` and ``no_pushdown``; each query is observed
  once (the first tensor run records key domains on the host), then runs
  jitted on the chip;
- ``sf10``: TPC-H SF10 (``sf=1000``, 60M lineitem rows), Q1 and Q5 under
  ``no_pushdown``;
- ``stream``: one ``run_stream`` over the 15 queries at SF1 with the
  storage side in worker processes (``storage_tier="process"``);
- ``kernels``: the six Pallas kernels of ``kernels/ops.py``, compiled for
  the chip, at 2^22 rows against ``kernels/ref.py``.

It stops with a non-zero exit and prints no result when JAX finds no TPU,
when an answer differs from the oracle, when a residual raises, runs on the
interpreter or leaves the chip, or when a worker process loaded the TPU
library. The times it prints are those of a smoke run, not benchmark
figures. The last line of standard output is one JSON object naming the
device.

    python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SF1, SF10 = 100, 1000            # generator sf is 1/100 of TPC-H's SF
SF10_QUERIES = ("Q1", "Q5")
KERNEL_ROWS = 1 << 22
MODES = ("adaptive", "no_pushdown")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(**fields) -> None:
    print("smoke " + json.dumps(fields, default=str), flush=True)


def max_rel_diff(want, got) -> float:
    """Largest difference of a float column from the oracle, relative to
    max(|oracle|, 1), rows aligned as ``engine.results_equal`` aligns
    them (exact columns lead the sort). Call after that check passed."""
    import numpy as np
    cols = sorted(want.columns)
    floats = [c for c in cols if np.asarray(want.cols[c]).dtype.kind == "f"]
    keys = floats + [c for c in cols if c not in floats]

    def order(t):
        return np.lexsort(tuple(np.asarray(t.cols[c]) for c in keys))

    ia, ib = order(want), order(got)
    worst = 0.0
    for c in floats:
        x = np.asarray(want.cols[c], np.float64)[ia]
        y = np.asarray(got.cols[c], np.float64)[ib]
        d = np.abs(x - y) / np.maximum(np.abs(x), 1.0)
        worst = max(worst, float(d.max(initial=0.0)))
    return worst


def check_run(label: str, qid: str, want, run, platform: str) -> dict:
    """One tensor-residual ``QueryRun`` (or stream entry) against the
    oracle's table; returns the fields worth printing."""
    from repro.core import engine
    result = run["result"] if isinstance(run, dict) else run.result
    info = (run["residual_jit"] if isinstance(run, dict)
            else run.residual_jit)
    check(info is not None, f"{label} {qid}: residual ran on the interpreter")
    check(engine.results_equal(want, result),
          f"{label} {qid}: result differs from the interpreter oracle")
    if not info["observed"] and not info["fell_back"]:
        check(info["platforms"] == (platform,),
              f"{label} {qid}: residual outputs on {info['platforms']}, "
              f"not {platform}")
    return {"hits": info["hits"], "misses": info["misses"],
            "observed": info["observed"], "fell_back": info["fell_back"],
            "max_rel_diff": max_rel_diff(want, result)}


def query_phase(label: str, catalog, queries: dict, modes, platform: str,
                oracle: dict, warm: bool) -> None:
    """Each query: the oracle once (kept in ``oracle``), then per mode
    tensor runs until one has run jitted on the device — with ``warm``,
    from a warm jit cache. The first tensor run of a query is its observe
    pass, the first jitted run compiles."""
    from repro.core import engine
    for qid, q in queries.items():
        t0 = time.perf_counter()
        oracle[qid] = engine.run_query(q, catalog, engine.EngineConfig()).result
        log(phase=label, qid=qid, oracle_s=time.perf_counter() - t0,
            rows=len(oracle[qid]))
        for mode in modes:
            cfg = engine.EngineConfig(mode=mode, residual="tensor")
            for step in range(4):
                t0 = time.perf_counter()
                run = engine.run_query(q, catalog, cfg)
                secs = time.perf_counter() - t0
                fields = check_run(f"{label}/{mode}", qid, oracle[qid], run,
                                   platform)
                log(phase=label, qid=qid, mode=mode, run=step, seconds=secs,
                    **fields)
                jitted = not fields["observed"] and not fields["fell_back"]
                if jitted and (fields["misses"] == 0 or not warm):
                    break
            check(jitted and (fields["misses"] == 0 or not warm),
                  f"{label}/{mode} {qid}: no jitted run on the device")


def stream_phase(catalog, queries: dict, platform: str,
                 oracle: dict) -> None:
    """The served path on the process storage tier: storage workers are
    spawned while this process holds the chip, so each must have stayed
    off it (no libtpu mapped into the worker)."""
    from repro.core import engine, runtime
    from repro.distributed import workers
    cfg = engine.EngineConfig(residual="tensor", storage_tier="process")
    stream = [runtime.StreamQuery(q, arrival=0.02 * i)
              for i, q in enumerate(queries.values())]
    try:
        t0 = time.perf_counter()
        run = runtime.run_stream(stream, catalog, cfg)
        wall = time.perf_counter() - t0
        for qid, entry in run.per_query.items():
            fields = check_run("stream", qid, oracle[qid],
                               dict(entry, result=run.results[qid]), platform)
            check(not fields["observed"] and not fields["fell_back"],
                  f"stream {qid}: residual did not run jitted")
            log(phase="stream", qid=qid, finish_s=entry["finish_s"],
                **fields)
        pool = workers.pool_for(catalog, pd_slots=cfg.res.pd_slots)
        for node, ch in pool.channels.items():
            with open(f"/proc/{ch.proc.pid}/maps") as f:
                check("libtpu" not in f.read(),
                      f"storage worker {node} loaded the TPU library")
        log(phase="stream", queries=len(run.results), wall_s=wall,
            n_pushdown=run.n_pushdown, n_pushback=run.n_pushback,
            worker_pids=[ch.proc.pid for ch in pool.channels.values()])
    finally:
        workers.close_all_pools()


def kernel_phase(seed: int, rows: int, platform: str) -> None:
    """Each ``kernels/ops.py`` wrapper compiled for the device (on a TPU a
    Mosaic ``tpu_custom_call`` in the program, so nothing is interpreted)
    and checked against its ``kernels/ref.py`` oracle on the same
    inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.queryproc.expressions import Col

    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.uniform(k[0], (rows,), jnp.float32, 0, 50)
    d = jax.random.uniform(k[1], (rows,), jnp.float32, 0, 10)
    ids = jax.random.randint(k[2], (rows,), 0, 37, jnp.int32)
    vals = jax.random.uniform(k[3], (rows,), jnp.float32)
    keys = jax.random.randint(k[4], (rows,), 0, 1 << 30, jnp.int32)
    pred = ops.compile_predicate((Col("q") <= 24)
                                 & ((Col("d") > 5) | Col("q").eq(7)))
    cols = {"q": q, "d": d}
    words = ref.predicate_bitmap(cols, pred)
    masked, block_counts = ref.bitmap_apply(words, vals)
    pids, block_hist = ref.hash_partition(keys, 16)

    def exact(a, b):
        return np.array_equal(np.asarray(a), np.asarray(b))

    def close(a, b):
        return np.allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-2)

    def agg_agrees(out, want):
        return close(out[0], want[0]) and exact(out[1], want[1])

    # name -> (wrapper, its arguments, check of its output against ref)
    cases = {
        "predicate_bitmap": (
            lambda q, d: ops.predicate_bitmap({"q": q, "d": d}, pred),
            (q, d), lambda out: exact(out, words)),
        "bitmap_apply": (
            ops.bitmap_apply, (words, vals),
            lambda out: exact(out[0], masked)
            and int(out[1]) == int(block_counts.sum())),
        "grouped_agg": (
            lambda i, v: ops.grouped_agg(i, v, 37), (ids, vals),
            lambda out: agg_agrees(out, ref.grouped_agg(ids, vals, 37))),
        "hash_partition": (
            lambda k: ops.hash_partition(k, 16), (keys,),
            lambda out: exact(out[0], pids)
            and exact(out[1], block_hist.sum(axis=0))),
        "fused_scan_agg": (
            lambda q, d, i, v: ops.fused_scan_agg({"q": q, "d": d}, pred,
                                                  i, v, 37),
            (q, d, ids, vals),
            lambda out: agg_agrees(
                out, ref.fused_scan_agg(cols, pred, ids, vals, 37))),
        "fused_scan_shuffle": (
            lambda q, d, k: ops.fused_scan_shuffle({"q": q, "d": d}, pred,
                                                   k, 16),
            (q, d, keys),
            lambda out: all(exact(a, b) for a, b in zip(
                out, ref.fused_scan_shuffle(cols, pred, keys, 16)))),
    }
    for name, (fn, fargs, agrees) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*fargs).compile()
        t_compile = time.perf_counter() - t0
        check(platform != "tpu" or "tpu_custom_call" in compiled.as_text(),
              f"kernel {name}: no Mosaic kernel in the compiled program")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*fargs))
        t_run = time.perf_counter() - t0
        check(agrees(out), f"kernel {name}: differs from kernels/ref.py")
        log(phase="kernels", kernel=name, rows=rows, compile_s=t_compile,
            first_run_s=t_run)


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated TPC-H data and kernel inputs")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: run from a checkout of the repository "
              f"({src}/repro not found)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {device}", file=sys.stderr)
        return 1

    from repro import jaxcache
    from repro.compiler import compile_query
    from repro.compiler.tpch_ir import QUERY_IDS
    from repro.obs.metrics import get_metrics
    from repro.queryproc import tpch

    cache_dir = jaxcache.enable_compile_cache()
    log(phase="start", note="smoke run: times are not benchmark figures",
        device=device, seed=args.seed, compile_cache=cache_dir,
        cache_entries=cache_entries(cache_dir))
    t_start = time.perf_counter()
    try:
        kernel_phase(args.seed, KERNEL_ROWS, dev.platform)

        t0 = time.perf_counter()
        cat = tpch.build_catalog(sf=SF1, seed=args.seed, num_nodes=2)
        log(phase="sf1", build_s=time.perf_counter() - t0)
        queries = {qid: compile_query(qid) for qid in QUERY_IDS}
        oracle: dict = {}
        query_phase("sf1", cat, queries, MODES, dev.platform, oracle,
                    warm=True)
        stream_phase(cat, queries, dev.platform, oracle)
        del cat

        t0 = time.perf_counter()
        cat = tpch.build_catalog(sf=SF10, seed=args.seed, num_nodes=2)
        log(phase="sf10", build_s=time.perf_counter() - t0)
        query_phase("sf10", cat, {q: compile_query(q) for q in SF10_QUERIES},
                    ("no_pushdown",), dev.platform, {}, warm=False)

        m = get_metrics()
        errors = m.counter("residual.errors").value
        check(errors == 0, f"{errors} residual errors")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(phase="end", seconds=time.perf_counter() - t_start,
        residual_fallbacks=m.counter("residual.fallbacks").value,
        jit_hits=m.counter("residual.jit_cache.hits").value,
        jit_misses=m.counter("residual.jit_cache.misses").value,
        cache_entries=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
