"""Fused batched executor == per-partition reference, byte for byte.

The batched executor (core.executor) must be indistinguishable from the
interpretive per-partition path (core.plan.execute_push_plan): identical
merged tables (same columns, dtypes, values, row order) for every TPC-H
query plan, identical end-to-end results in all four engine modes, and
identical cost estimates. Property tests cover segment-keyed partial
aggregation over adversarial partitionings (hypothesis optional: a
deterministic sweep covers the same invariants when absent)."""
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dependency — see pyproject.toml [test]
    HAVE_HYPOTHESIS = False

from repro import obs
from repro.core import engine
from repro.core.executor import CompiledPushPlan, compile_push_plan
from repro.core.plan import PushPlan, estimate_cost, execute_push_plan
from repro.queryproc import queries as Q
from repro.queryproc import tpch
from repro.queryproc.expressions import Col
from repro.queryproc.table import ColumnTable

CAT = tpch.build_catalog(sf=1.0, num_nodes=2, rows_per_partition=4_000)


def assert_tables_identical(a: ColumnTable, b: ColumnTable, ctx=""):
    assert a.columns == b.columns, (ctx, a.columns, b.columns)
    for c in a.columns:
        x, y = a.cols[c], b.cols[c]
        assert x.dtype == y.dtype, (ctx, c, x.dtype, y.dtype)
        assert np.array_equal(x, y, equal_nan=True), (ctx, c)


def _check_batch_equals_reference(plan: PushPlan, parts):
    ref = ColumnTable.concat([execute_push_plan(plan, p)[0] for p in parts])
    bat = compile_push_plan(plan).execute_batch(parts)
    assert_tables_identical(ref, bat, plan.table)


# ------------------------------------------------- all queries, all modes
@pytest.mark.parametrize("qid", Q.QUERY_IDS)
def test_merged_tables_byte_identical(qid):
    """Per-(table, plan) merged pushdown results are byte-identical."""
    q = Q.build_query(qid)
    for table, plan in q.plans.items():
        parts = [p.data for p in CAT.partitions_of(table)]
        _check_batch_equals_reference(plan, parts)


@pytest.mark.parametrize("threshold", [0.0, 1.5])
@pytest.mark.parametrize("qid", Q.QUERY_IDS)
def test_adaptive_filter_branches_byte_identical(qid, threshold):
    """Both adaptive filter-stage branches (forced concat-everything at
    threshold 0, forced gather-survivors at 1.5) produce the same bytes as
    the reference — the branch choice is purely a performance decision."""
    q = Q.build_query(qid)
    for table, plan in q.plans.items():
        parts = [p.data for p in CAT.partitions_of(table)]
        ref = ColumnTable.concat(
            [execute_push_plan(plan, p)[0] for p in parts])
        bat = compile_push_plan(plan).execute_batch(parts,
                                                    threshold=threshold)
        assert_tables_identical(ref, bat, (qid, table, threshold))


@pytest.mark.parametrize("qid", Q.QUERY_IDS)
def test_batch_parts_byte_identical(qid):
    """execute_batch_parts splits the fused pass back into per-partition
    tables identical to each per-partition reference result."""
    q = Q.build_query(qid)
    for table, plan in q.plans.items():
        parts = [p.data for p in CAT.partitions_of(table)]
        got, aux = compile_push_plan(plan).execute_batch_parts(parts)
        for p, g, a in zip(parts, got, aux):
            ref, ref_aux = execute_push_plan(plan, p)
            assert_tables_identical(ref, g, (qid, table))
            assert ref_aux == a == {}


# ------------------------------------------- aux outputs: bitmap, shuffle
@pytest.mark.parametrize("qid", Q.QUERY_IDS)
def test_bitmap_only_batch_byte_identical(qid):
    """The §4.2 bitmap-emission path: every predicate-bearing plan's
    bitmap_only variant produces per-partition packed bitmaps and filtered
    tables identical to the per-partition reference."""
    import dataclasses
    q = Q.build_query(qid)
    checked = 0
    for table, plan in q.plans.items():
        if plan.predicate is None or plan.apply_bitmap:
            continue
        bplan = dataclasses.replace(plan, bitmap_only=True)
        parts = [p.data for p in CAT.partitions_of(table)]
        got, aux = compile_push_plan(bplan).execute_batch_parts(parts)
        for p, g, a in zip(parts, got, aux):
            ref, ref_aux = execute_push_plan(bplan, p)
            assert_tables_identical(ref, g, (qid, table))
            np.testing.assert_array_equal(ref_aux["bitmap"], a["bitmap"])
        checked += 1
    if qid != "Q18":      # Q18's fact predicate lives above the pushed agg
        assert checked, f"{qid}: no predicate-bearing plan exercised"


@pytest.mark.parametrize("qid", Q.QUERY_IDS)
def test_shuffle_batch_byte_identical(qid):
    """The §4.2 shuffle path: per-partition hash-partition slices and
    position vectors from the batch pass match the reference exactly."""
    import dataclasses
    q = Q.build_query(qid)
    for table, plan in q.plans.items():
        # the shuffle key must be in the plan's output schema
        key = q.shuffle_keys.get(table)
        if key is None or key not in plan.columns:
            key = next((c for c in plan.columns if c in
                        CAT.partitions_of(table)[0].data.cols), None)
        if key is None:
            continue
        splan = dataclasses.replace(plan, shuffle=(key, 4))
        parts = [p.data for p in CAT.partitions_of(table)]
        got, aux = compile_push_plan(splan).execute_batch_parts(parts)
        for p, g, a in zip(parts, got, aux):
            ref, ref_aux = execute_push_plan(splan, p)
            assert_tables_identical(ref, g, (qid, table))
            np.testing.assert_array_equal(ref_aux["position_vector"],
                                          a["position_vector"])
            assert len(ref_aux["shuffle_parts"]) == len(a["shuffle_parts"])
            for rp, bp in zip(ref_aux["shuffle_parts"], a["shuffle_parts"]):
                assert_tables_identical(rp, bp, (qid, table, key))


def test_single_partition_execute_emits_aux():
    """CompiledPushPlan.execute now serves aux-producing plans too."""
    import dataclasses
    plan = Q.build_query("Q3").plans["lineitem"]  # filter+derive, no agg
    part = CAT.partitions_of("lineitem")[0].data
    for variant in (dataclasses.replace(plan, bitmap_only=True),
                    dataclasses.replace(plan, shuffle=("l_orderkey", 4))):
        ref, ref_aux = execute_push_plan(variant, part)
        got, aux = compile_push_plan(variant).execute(part)
        assert_tables_identical(ref, got)
        assert set(ref_aux) == set(aux)
        for k in ref_aux:
            if k == "shuffle_parts":
                for rp, bp in zip(ref_aux[k], aux[k]):
                    assert_tables_identical(rp, bp)
            else:
                np.testing.assert_array_equal(ref_aux[k], aux[k])


def test_filter_decision_log():
    """Each predicate-bearing batch records its adaptive branch choice."""
    from repro.core import executor as X
    q = Q.build_query("Q6")
    reqs = engine.plan_requests(q, CAT)
    X.reset_filter_decisions()
    engine.execute_requests(reqs, filter_gather_threshold=1.5)
    counts = X.filter_decision_counts()
    assert counts["gather"] >= 1 and counts["concat"] == 0
    X.reset_filter_decisions()
    engine.execute_requests(reqs, filter_gather_threshold=0.0)
    counts = X.filter_decision_counts()
    assert counts["concat"] >= 1 and counts["gather"] == 0
    d = obs.filter_decision_channel().snapshot()[0]
    assert d["table"] == "lineitem" and 0.0 <= d["est_selectivity"] <= 1.0
    X.reset_filter_decisions()


@pytest.mark.parametrize("qid", Q.QUERY_IDS)
@pytest.mark.parametrize("mode", engine.MODES)
def test_end_to_end_byte_identical(qid, mode):
    """Final query results agree bit-for-bit between executors, per mode."""
    q = Q.build_query(qid)
    rb = engine.run_query(q, CAT, engine.EngineConfig(
        mode=mode, executor=engine.EXECUTOR_BATCHED))
    rr = engine.run_query(q, CAT, engine.EngineConfig(
        mode=mode, executor=engine.EXECUTOR_REFERENCE))
    assert_tables_identical(rb.result, rr.result, (qid, mode))
    # scheduling outcomes don't depend on the executor either
    assert rb.n_admitted == rr.n_admitted
    assert rb.n_pushed_back == rr.n_pushed_back


def test_compiled_cost_identical():
    """CompiledPushPlan.estimate_cost memoizes the plan-level invariants
    but must reproduce plan.estimate_cost exactly, every partition."""
    for qid in Q.QUERY_IDS:
        q = Q.build_query(qid)
        for table, plan in q.plans.items():
            cplan = compile_push_plan(plan)
            assert cplan.accessed == plan.accessed_columns()
            for part in CAT.partitions_of(table):
                assert cplan.estimate_cost(part) == estimate_cost(plan, part), \
                    (qid, table, part.index)


def test_compile_memoized_per_plan():
    plan = Q.build_query("Q1").plans["lineitem"]
    assert compile_push_plan(plan) is compile_push_plan(plan)
    # a structurally-equal but distinct plan object compiles separately
    import dataclasses
    clone = dataclasses.replace(plan)
    assert compile_push_plan(clone) is not compile_push_plan(plan)


# ------------------------------------------ segment-keyed partial aggs
def _random_parts(rng, n_parts, allow_empty=True):
    """A random table split into contiguous partitions (some possibly
    empty — a filter can drain a partition, and the batch path must keep
    segment bookkeeping straight)."""
    sizes = [int(rng.integers(0 if allow_empty else 1, 400))
             for _ in range(n_parts)]
    n = sum(sizes)
    tab = {
        "k1": rng.integers(0, 5, n).astype(np.int32),
        "k2": rng.integers(0, 3, n).astype(np.int32),
        "v_f": rng.normal(size=n),
        "v_i": rng.integers(-50, 50, n).astype(np.int32),
        "x": rng.uniform(0, 100, n),
    }
    parts, at = [], 0
    for s in sizes:
        parts.append(ColumnTable({k: v[at:at + s] for k, v in tab.items()}))
        at += s
    return parts


AGGS = (("s", "sum", "v_f"), ("mn", "min", "v_i"), ("mx", "max", "v_f"),
        ("avg", "mean", "v_f"), ("cnt", "count", ""))


def _check_segmented_agg(seed, n_parts, n_keys, with_pred):
    rng = np.random.default_rng(seed)
    parts = _random_parts(rng, n_parts)
    keys = ("k1", "k2")[:n_keys]
    plan = PushPlan(
        "t", tuple(keys),
        predicate=(Col("x") < 60) if with_pred else None,
        agg=(tuple(keys), AGGS))
    _check_batch_equals_reference(plan, parts)


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 10**6), st.integers(1, 8), st.integers(0, 2),
           st.booleans())
    @settings(max_examples=25, deadline=None)
    def test_segmented_agg_property(seed, n_parts, n_keys, with_pred):
        _check_segmented_agg(seed, n_parts, n_keys, with_pred)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("n_keys", [0, 1, 2])
@pytest.mark.parametrize("with_pred", [False, True])
def test_segmented_agg_deterministic(seed, n_keys, with_pred):
    _check_segmented_agg(seed, n_parts=1 + seed % 6, n_keys=n_keys,
                         with_pred=with_pred)


@pytest.mark.parametrize("n_keys", [0, 1])
def test_agg_then_topk(n_keys):
    """agg + top_k in one plan: the top-k must segment the agg *output*
    (rows collapsed to groups), not the filtered input rows."""
    rng = np.random.default_rng(13)
    parts = _random_parts(rng, 5)
    keys = ("k1",)[:n_keys]
    plan = PushPlan("t", tuple(keys), predicate=Col("x") < 80,
                    agg=(tuple(keys), (("s", "sum", "v_f"),)),
                    top_k=("s", 3, False))
    _check_batch_equals_reference(plan, parts)


@pytest.mark.parametrize("seed", range(4))
def test_segmented_topk(seed):
    rng = np.random.default_rng(seed)
    parts = _random_parts(rng, 5)
    plan = PushPlan("t", ("k1", "v_f"), predicate=Col("x") < 70,
                    top_k=("v_f", 7, bool(seed % 2)))
    _check_batch_equals_reference(plan, parts)


@pytest.mark.parametrize("seed", range(4))
def test_segmented_derive_project(seed):
    rng = np.random.default_rng(seed)
    parts = _random_parts(rng, 6)
    plan = PushPlan(
        "t", ("k1", "dbl"), predicate=(Col("v_i") > 0) | (Col("x") < 20),
        derive=(("dbl", ("v_f", "x"), lambda a, b: a * b + 1.0),))
    _check_batch_equals_reference(plan, parts)


def test_all_partitions_filtered_out():
    rng = np.random.default_rng(7)
    parts = _random_parts(rng, 4, allow_empty=False)
    plan = PushPlan("t", ("k1",), predicate=Col("x") > 1e9,
                    agg=(("k1",), (("s", "sum", "v_f"), ("c", "count", ""))))
    _check_batch_equals_reference(plan, parts)


def test_grouped_minmax_reduceat_matches_loop():
    """The reduceat vectorization of grouped min/max (operators.py) equals
    the per-segment loop it replaced."""
    from repro.queryproc import operators as ops
    rng = np.random.default_rng(3)
    n = 5000
    t = ColumnTable({"k": rng.integers(0, 40, n).astype(np.int32),
                     "v": rng.normal(size=n)})
    out = ops.grouped_agg(t, ["k"], {"lo": ("min", "v"), "hi": ("max", "v")})
    want_lo = [t.cols["v"][t.cols["k"] == k].min()
               for k in np.unique(t.cols["k"])]
    want_hi = [t.cols["v"][t.cols["k"] == k].max()
               for k in np.unique(t.cols["k"])]
    np.testing.assert_array_equal(out.cols["lo"], want_lo)
    np.testing.assert_array_equal(out.cols["hi"], want_hi)


# ------------------------------------------------- compiled expressions
def test_compile_expr_bitwise_equals_evaluate():
    from repro.queryproc import expressions as ex
    rng = np.random.default_rng(11)
    t = ColumnTable({"a": rng.uniform(0, 100, 4096),
                     "b": rng.integers(0, 20, 4096).astype(np.int32),
                     "c": rng.uniform(0, 100, 4096)})
    exprs = [
        (Col("a") > 30) & (Col("b").isin([2, 5, 7])),
        (Col("a") < Col("c")) | Col("b").eq(3),
        Col("a").between(10, 90) & ((Col("b") >= 4) | (Col("c") <= 50)),
    ]
    for e in exprs:
        np.testing.assert_array_equal(ex.compile_expr(e)(t.cols),
                                      ex.evaluate(e, t))


def test_compile_selectivity_equals_estimate():
    from repro.queryproc import expressions as ex
    for qid in Q.QUERY_IDS:
        q = Q.build_query(qid)
        for table, plan in q.plans.items():
            if plan.predicate is None:
                continue
            for part in CAT.partitions_of(table):
                stats = part.data.stats()
                assert (ex.compile_selectivity(plan.predicate)(stats)
                        == ex.estimate_selectivity(plan.predicate, stats)), \
                    (qid, table)


# ------------------------------------------------------ engine plumbing
def test_execute_requests_groups_by_plan():
    q = Q.build_query("Q3")
    reqs = engine.plan_requests(q, CAT)
    ref = engine.execute_requests(reqs, engine.EXECUTOR_REFERENCE)
    bat = engine.execute_requests(reqs, engine.EXECUTOR_BATCHED)
    assert set(ref) == set(bat)
    for table in ref:
        assert_tables_identical(ref[table], bat[table], table)


def test_single_partition_execute():
    plan = Q.build_query("Q6").plans["lineitem"]
    part = CAT.partitions_of("lineitem")[0].data
    ref, _aux = execute_push_plan(plan, part)
    bat, _ = compile_push_plan(plan).execute(part)
    assert_tables_identical(ref, bat)


def test_fused_pallas_matches_batched_numpy():
    """The fused Pallas kernel (predicate -> mask -> grouped agg, one pass)
    agrees with the numpy batch executor on a pushed Q1-style plan."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    rng = np.random.default_rng(5)
    n = 6000
    ship = rng.uniform(0, 3000, n).astype(np.float32)
    flag = rng.integers(0, 3, n).astype(np.int32)
    qty = rng.uniform(1, 50, n).astype(np.float32)
    expr = Col("l_shipdate") <= 2000.0
    sums, counts = kops.fused_scan_agg(
        {"l_shipdate": jnp.asarray(ship)}, kops.compile_predicate(expr),
        jnp.asarray(flag), jnp.asarray(qty), 3, block=2048)
    parts = [ColumnTable({"l_shipdate": ship[i::2], "flag": flag[i::2],
                          "qty": qty[i::2]}) for i in range(2)]
    plan = PushPlan("t", ("flag",), predicate=expr,
                    agg=(("flag",), (("s", "sum", "qty"),
                                     ("c", "count", ""))))
    bat = compile_push_plan(plan).execute_batch(parts)
    # batch output is segment-major (partition, key): fold partials
    want_s = np.zeros(3)
    np.add.at(want_s, bat.cols["flag"], bat.cols["s"])
    want_c = np.zeros(3, np.int64)
    np.add.at(want_c, bat.cols["flag"], bat.cols["c"])
    np.testing.assert_allclose(np.asarray(sums), want_s, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(counts), want_c)
