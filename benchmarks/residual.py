"""Residual backend A/B: numpy interpreter vs tensorized jax.jit programs.

Wall-clock of the compute layer's residual evaluation (the post-pushdown
joins / aggregates / TopK) over the merged all-pushdown tables, per TPC-H
query: the ``compiler.interpreter`` oracle vs ``compiler.tensorize``'s
fused jit programs, **identity asserted outside the timed region** and
jit compilation measured separately (observe pass, first-jit cold pass,
then warm best-of-N — only warm runs race the interpreter; that is the
steady state the engine sees, since the shape-bucketed jit cache makes
every later same-bucket execution warm).

The guarded headline is the **residual-dominant subset** (multi-join
probe pipelines: Q4/Q5/Q7/Q8/Q18, where the residual is join+aggregate
over 10k-100k merged rows). Tiny-input queries (Q1/Q6 ship a handful of
pre-aggregated rows) and the lexsort-aggregate outlier (Q3's huge-domain
multi-key group) run interpreter-side under ``residual="auto"`` anyway —
they are reported, not guarded. ``residual_ok`` (CI-enforced by
``benchmarks.perf_guard``) = every query identical, no fallbacks, and
subset speedup >= the 1.3x floor.
"""
from __future__ import annotations

from repro.compiler import compile_query_detailed, interpreter, tensorize
from repro.compiler.tpch_ir import QUERY_IDS
from repro.core import engine
from repro.queryproc.table import ColumnTable

from benchmarks import common

# residual-dominant: the residual is a multi-join probe pipeline over the
# fact table's merged rows — the workload the tensor backend targets
SUBSET = ("Q4", "Q5", "Q7", "Q8", "Q18")
SUBSET_FLOOR = 1.3   # acceptance: CI-enforced minimum subset speedup

# the CI perf smoke shares this exact configuration
REAL_QUICK_KWARGS = {"repeats": 3, "sf": 2.0}


def _merged_tables(cq, cat):
    """All-pushdown merged inputs (identical for any decision vector —
    pinned by tests/test_runtime.py — so one vector suffices here)."""
    out = {}
    for t, plan in cq.plans.items():
        parts = [engine.execute_push_plan(plan, p.data)[0]
                 for p in cat.partitions_of(t)]
        out[t] = ColumnTable.concat(parts)
    return out


def run_real(qids=tuple(QUERY_IDS), repeats: int = 3, sf: float = None,
             subset=SUBSET) -> dict:
    sf = sf or common.SF
    cat = common.catalog(num_nodes=2, sf=sf)
    queries = {}
    all_ok = True
    no_fallback = True
    for qid in qids:
        cq = compile_query_detailed(qid)
        merged = _merged_tables(cq, cat)
        rows = sum(len(t) for t in merged.values())
        ref = interpreter.run(cq.residual, merged)
        # outside the timed region: observe pass, first jit, identity
        with common.Timer() as t_obs:
            tensorize.execute(cq.residual, merged)
        with common.Timer() as t_jit:
            r_cold = tensorize.execute(cq.residual, merged)
        r_warm = tensorize.execute(cq.residual, merged)
        identical = engine.results_equal(ref, r_warm.table)
        all_ok &= identical
        no_fallback &= not (r_cold.fell_back or r_warm.fell_back)
        t_int = common.best_time(
            lambda: interpreter.run(cq.residual, merged), repeats)
        t_ten = common.best_time(
            lambda: tensorize.execute(cq.residual, merged), repeats)
        queries[qid] = {
            "rows_in": rows, "n_stages": r_warm.n_stages,
            "jit_hits_warm": r_warm.jit_hits,
            "fell_back": bool(r_cold.fell_back or r_warm.fell_back),
            "t_observe_ms": 1e3 * t_obs.elapsed,
            "t_first_jit_ms": 1e3 * t_jit.elapsed,
            "t_reference_ms": 1e3 * t_int,   # interpreter
            "t_batched_ms": 1e3 * t_ten,     # tensor, warm jit cache
            "speedup": t_int / max(t_ten, 1e-12),
            "identical": identical}
    sub = [q for q in subset if q in queries]
    sub_ref = sum(queries[q]["t_reference_ms"] for q in sub)
    sub_ten = sum(queries[q]["t_batched_ms"] for q in sub)
    sub_speed = sub_ref / max(sub_ten, 1e-12)
    out = common.summarize_real(
        queries, sf, repeats,
        subset=list(sub), subset_speedup=sub_speed,
        subset_floor=SUBSET_FLOOR,
        residual_ok=bool(all_ok and no_fallback
                         and sub_speed >= SUBSET_FLOOR))
    out["all_identical"] = all_ok
    return out


def _headline(real: dict):
    h = common.real_headline(real)
    if h is None:
        return None
    h.update(subset_speedup=round(real["subset_speedup"], 3),
             residual_ok=real["residual_ok"],
             all_identical=real["all_identical"])
    return h


def update_root_bench(out: dict):
    return common.update_root_bench_real("residual", out,
                                         headline_fn=_headline)


def render_real(out: dict) -> str:
    rows = [[qid, v["rows_in"], v["n_stages"],
             "fb" if v["fell_back"] else "-",
             f"{v['t_observe_ms']:.1f}", f"{v['t_first_jit_ms']:.1f}",
             f"{v['t_reference_ms']:.2f}", f"{v['t_batched_ms']:.2f}",
             f"{v['speedup']:.2f}x"] for qid, v in out["queries"].items()]
    hdr = ["query", "rows_in", "stages", "fb", "observe_ms", "jit_ms",
           "interp_ms", "tensor_ms", "speedup"]
    return common.table(rows, hdr) + (
        f"\nresidual backend A/B (warm jit cache): total "
        f"{out['total_reference_ms']:.1f}ms -> "
        f"{out['total_batched_ms']:.1f}ms ({out['total_speedup']:.2f}x; "
        f"geomean {out['geomean_speedup']:.2f}x)\n"
        f"residual-dominant subset {'+'.join(out['subset'])}: "
        f"{out['subset_speedup']:.2f}x (floor {out['subset_floor']:.1f}x) "
        f"residual_ok={out['residual_ok']} "
        f"all_identical={out['all_identical']}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--real-quick", action="store_true",
                    help="sf=2 configuration (CI perf smoke)")
    args = ap.parse_args()
    o = run_real(**REAL_QUICK_KWARGS) if args.real_quick else run_real()
    if not args.real_quick:
        common.save_report("residual_backend", o)
    update_root_bench(o)
    print(render_real(o))
