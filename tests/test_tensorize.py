"""Tensorized compute residual (compiler.tensorize + engine dispatch).

The load-bearing invariant: the interpreter is the oracle. For every
TPC-H residual, every engine mode, every decision vector, warm or cold
jit caches, and fault-demoted replays, the tensor backend's table is
identical (``engine.results_equal``) to the interpreter's. On top: the
observe -> jit-miss -> jit-hit protocol is pinned via ``TensorRun``
counters, shape buckets share compiled programs, out-of-domain keys
respecialize (gen bump) without changing results, duplicate-right-key
joins fall back gracefully, and ``compile_expr_jnp`` matches
``compile_expr`` bitwise on random columns.
"""
import contextlib
import os
import time
import types

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from repro.compiler import (compile_query, compile_query_detailed,
                            interpreter, ir, tensorize)
from repro.compiler.tpch_ir import QUERY_IDS
from repro.core import engine, runtime
from repro.core.arbitrator import PUSHBACK, PUSHDOWN
from repro.obs import metrics as om
from repro.queryproc import expressions as ex
from repro.queryproc import tpch
from repro.queryproc.expressions import Col
from repro.queryproc.expressions_jax import compile_expr_jnp
from repro.queryproc.table import ColumnTable

CAT = tpch.build_catalog(sf=0.5, num_nodes=2, rows_per_partition=4_000)
CFG = engine.EngineConfig(mode="eager")


@pytest.fixture
def metrics():
    """A fresh registry, so a test reads only its own residual counters."""
    prev = om.get_metrics()
    m = om.Metrics()
    om.set_metrics(m)
    yield m
    om.set_metrics(prev)


def merged_for(cq):
    """All-pushdown merged tables (identical for any decision vector)."""
    out = {}
    for t, plan in cq.plans.items():
        parts = [engine.execute_push_plan(plan, p.data)[0]
                 for p in CAT.partitions_of(t)]
        out[t] = ColumnTable.concat(parts)
    return out


# ------------------------------------------------ all-15 oracle identity
@pytest.mark.parametrize("qid", QUERY_IDS)
def test_tensor_matches_interpreter(qid, metrics):
    """observe -> first jit (miss) -> warm (hit): all three runs return
    the interpreter's exact table, and the warm run hits every stage —
    on the jitted path itself: no error and no fallback was counted, so a
    residual that silently settled on the oracle cannot pass."""
    cq = compile_query_detailed(qid)
    merged = merged_for(cq)
    ref = interpreter.run(cq.residual, merged)
    r_obs = tensorize.execute(cq.residual, merged)
    r_cold = tensorize.execute(cq.residual, merged)
    r_warm = tensorize.execute(cq.residual, merged)
    assert r_obs.observed and not r_cold.observed and not r_warm.observed
    for r in (r_obs, r_cold, r_warm):
        assert engine.results_equal(ref, r.table), qid
        assert not r.fell_back, qid
    # every jittable stage misses cold and hits warm (a stage may be
    # host-only — e.g. Q22's PyOp tail — and then touches no jit cache)
    assert r_cold.jit_hits == 0 and r_cold.jit_misses >= 1
    assert r_warm.jit_misses == 0
    assert r_warm.jit_hits == r_cold.jit_misses
    assert r_warm.platforms == ("cpu",)
    assert metrics.counter("residual.errors").value == 0
    assert metrics.counter("residual.fallbacks").value == 0


def _warm(qid):
    """A compiled query whose residual has observed and compiled through
    the engine (which names its stages), and the tensor config."""
    q = compile_query(qid)
    cfg = engine.EngineConfig(mode="eager", residual="tensor")
    engine.run_query(q, CAT, cfg)                    # observe
    engine.run_query(q, CAT, cfg)                    # compile
    return q, cfg


def _capture_inputs(monkeypatch, art):
    """Record the device inputs each jitted stage is called with."""
    seen = {}

    def rec(i, f):
        def call(inputs):
            seen[i] = inputs
            return f(inputs)
        return call

    monkeypatch.setattr(art, "jit_fns", [
        None if f is None else rec(i, f) for i, f in enumerate(art.jit_fns)])
    return seen


def test_stage_modules_are_named_after_query_and_index(monkeypatch):
    """Each jitted stage's XLA module reads ``jit_residual_<qid>_s<i>``,
    and the name is the only change to the lowered program."""
    import jax
    q, cfg = _warm("Q18")
    art = tensorize._artifact(q.residual)
    fns = art.jit_fns
    seen = _capture_inputs(monkeypatch, art)
    engine.run_query(q, CAT, cfg)
    assert seen
    for i, inputs in seen.items():
        name = tensorize.stage_name("Q18", i)
        assert name == f"residual_Q18_s{i}"
        with jax.enable_x64(True):
            text = fns[i].lower(inputs).as_text()
            art.qid = None
            plain = jax.jit(tensorize._make_stage_fn(art.stages[i], art))
            base = plain.lower(inputs).as_text()
            art.qid = "Q18"
        assert f"jit_{name}" in text and "stage_fn" not in text
        assert text.replace(name, "N") == base.replace(f"residual_s{i}", "N")
    assert tensorize.stage_name("Q1#2", 0) == "residual_Q1_2_s0"


def test_h2d_bytes_count_the_padded_inputs_and_luts(monkeypatch, metrics):
    """``residual.h2d_bytes`` grows by exactly the bytes of what one call
    puts on the device: bucket-padded columns, validity masks, join
    LUTs and their key offsets."""
    import jax
    q, cfg = _warm("Q3")
    art = tensorize._artifact(q.residual)
    seen = _capture_inputs(monkeypatch, art)
    before = metrics.counter("residual.h2d_bytes").value
    engine.run_query(q, CAT, cfg)
    leaves = jax.tree_util.tree_leaves(seen)
    assert any("lut" in inp for st in seen.values() for inp in st.values())
    assert all(len(a.shape) == 0 or a.shape[0] >= tensorize._MIN_BUCKET
               for a in leaves)
    got = metrics.counter("residual.h2d_bytes").value - before
    assert got == sum(a.nbytes for a in leaves) > 0


RESIDUAL_STEPS = ("residual_prep", "residual_h2d", "residual_device",
                  "residual_d2h")


@pytest.fixture(scope="module")
def cat4():
    """A larger catalog, so that a residual's fixed host glue (dispatch,
    span bookkeeping, freeing its tables) is small beside its steps."""
    return tpch.build_catalog(sf=4, num_nodes=2, rows_per_partition=16_000)


def _steps(art, qid):
    """The child spans, with their attrs, that one warm call of ``art``
    opens inside ``residual_compute``: the four steps of each jitted
    stage, then its PyOp's host step where the stage has one."""
    want = []
    for st in art.stages:
        if st.jit_roots:
            want += [(n, {"qid": qid, "stage": st.index, "hit": True})
                     for n in RESIDUAL_STEPS]
        if st.pyop is not None:
            want.append(("residual_pyop", {"qid": qid, "stage": st.index}))
    return want


@pytest.fixture(scope="module")
def cat32():
    """Larger still, for the PyOp queries: at sf=4 Q15's and Q22's
    residuals last 1 to 3 ms on the CPU, and the fixed glue of a traced
    call (dispatch, the x64 context, span bookkeeping, about 0.1 ms) is
    4 to 8 % of that."""
    return tpch.build_catalog(sf=32, num_nodes=2, rows_per_partition=16_000)


PYOP_QIDS = ("Q15", "Q22")


@pytest.mark.parametrize("qid", ["Q3", "Q5", "Q10", "Q18", *PYOP_QIDS])
def test_residual_steps_cover_the_residual_span(qid, request):
    """Traced, each jitted stage call opens prep, h2d, device and d2h in
    that order, and a PyOp stage (Q15's, Q22's) its host step
    ``residual_pyop`` after them, all inside ``residual_compute``; the
    steps cover at least 95 % of it. Of three traced runs the best
    covered counts, so that a preemption of this process between two
    steps reads as noise."""
    from repro.obs.trace import tracing
    cat = request.getfixturevalue("cat32" if qid in PYOP_QIDS else "cat4")
    q = compile_query(qid)
    cfg = engine.EngineConfig(mode="eager", residual="tensor")
    engine.run_query(q, cat, cfg)                    # observe
    engine.run_query(q, cat, cfg)                    # compile
    want = _steps(tensorize._artifact(q.residual), qid)
    assert [n for n, _a in want].count("residual_pyop") == (
        qid in PYOP_QIDS)
    shares = []
    for _ in range(3):
        with tracing() as tr:
            engine.run_query(q, cat, cfg)
        (rc,) = tr.find("residual_compute")
        kids = sorted((s for s in tr.snapshot() if s.parent == rc.sid),
                      key=lambda s: s.t0)
        assert [(s.name, s.attrs) for s in kids] == want
        for k, s in enumerate(kids):
            assert rc.t0 <= s.t0 and s.t0 + s.dur <= rc.t0 + rc.dur
            if k:
                assert s.t0 >= kids[k - 1].t0 + kids[k - 1].dur
        assert not tr.find("residual_fallback")
        shares.append(sum(s.dur for s in kids) / rc.dur)
    assert max(shares) >= 0.95, shares


AGG_WAYS = ("dense", "scatter", "sort")


@pytest.mark.parametrize("qid,way", [("Q5", "dense"), ("Q10", "scatter"),
                                     ("Q18", "sort")])
def test_agg_way_counters_count_each_executed_aggregate(
        qid, way, cat4, metrics, monkeypatch):
    """``residual.agg.<way>`` grows by one per keyed aggregate a jitted
    stage call runs that way, on a call that compiles and on one that
    hits the jit cache alike; the observe run calls no stage. Q5 groups
    by nation (at most 25 codes). Q10 groups by customer: 150,000 codes
    at SF1 scatter, and Q18 by order key: 6M codes at SF1 sort. At this
    scale (4,000 customers, 60,000 orders) the test lowers the cap that
    sends each there."""
    if way == "scatter":
        monkeypatch.setattr(tensorize, "_AGG_DENSE_CAP", 1 << 10)
    if way == "sort":
        monkeypatch.setattr(tensorize, "_AGG_DOM_CAP", 1 << 12)
    q = compile_query(qid)
    cfg = engine.EngineConfig(mode="eager", residual="tensor")

    def counts():
        return {w: metrics.counter(f"residual.agg.{w}").value
                for w in AGG_WAYS}

    engine.run_query(q, cat4, cfg)                   # observe
    assert counts() == dict.fromkeys(AGG_WAYS, 0)
    runs = [engine.run_query(q, cat4, cfg).residual_jit for _ in range(2)]
    assert [(r["misses"], r["hits"]) for r in runs] == [(1, 0), (0, 1)]
    assert counts() == {w: 2 * (w == way) for w in AGG_WAYS}


def test_stage_error_raises_instead_of_falling_back(monkeypatch, metrics):
    """Only the designed guards (``TensorFallback``) replay the oracle. Any
    other failure inside a stage — a lowering, compile or device error —
    raises, counts in ``residual.errors`` and not as a fallback, and leaves
    the residual on the jitted path for the next run."""
    res = _agg_residual()
    merged = {"t": _tab(np.arange(64) % 4)}
    tensorize.execute(res, merged)                   # observe
    art = tensorize._artifact(res)

    def broken(inputs):
        raise RuntimeError("stage failed to compile")

    monkeypatch.setattr(art, "jit_fns", art.jit_fns[:-1] + [broken])
    with pytest.raises(RuntimeError, match="failed to compile"):
        tensorize.execute(res, merged)
    assert metrics.counter("residual.errors").value == 1
    assert metrics.counter("residual.fallbacks").value == 0
    assert not art.disabled
    monkeypatch.undo()
    ok = tensorize.execute(res, merged)
    assert not ok.fell_back
    assert engine.results_equal(interpreter.run(res, merged), ok.table)


def test_pyop_queries_partition_into_two_stages():
    """Q15/Q22 residuals contain a PyOp — the lowering must split into
    maximal jittable segments around it, not give up on the query."""
    for qid in ("Q15", "Q22"):
        cq = compile_query_detailed(qid)
        merged = merged_for(cq)
        tensorize.execute(cq.residual, merged)           # observe
        r = tensorize.execute(cq.residual, merged)
        assert r.n_stages == 2, qid
        assert not r.fell_back, qid


# ------------------------------------------- modes and decision vectors
@pytest.mark.parametrize("mode", engine.MODES)
def test_engine_modes_identical(mode):
    """Same query, same mode, both backends: identical results. The
    decision vector differs per mode; merged inputs do not — but the
    dispatch path (engine._run_decided) must behave under all four."""
    for qid in ("Q5", "Q22"):
        q = compile_query(qid)
        ri = engine.run_query(q, CAT, engine.EngineConfig(mode=mode))
        cfg_t = engine.EngineConfig(mode=mode, residual="tensor")
        engine.run_query(q, CAT, cfg_t)                  # observe
        rt = engine.run_query(q, CAT, cfg_t)
        assert engine.results_equal(ri.result, rt.result), (qid, mode)
        assert rt.residual_backend == "tensor"
        assert ri.residual_backend == "interpreter"


def test_random_decision_vectors_identical():
    """Hand-rolled pushdown/pushback splits: the merged tables are
    reassembly-identical, so the tensor residual must be too."""
    rng = np.random.default_rng(7)
    cq = compile_query_detailed("Q12")
    reqs = engine.plan_requests(cq.query, CAT)
    for _ in range(3):
        decisions = {r.req_id: (PUSHDOWN if rng.random() < 0.5 else PUSHBACK)
                     for r in reqs}
        split = runtime.execute_split(reqs, decisions, CFG.executor, None)
        ref = interpreter.run(cq.residual, split.merged)
        run = tensorize.execute(cq.residual, split.merged)
        assert engine.results_equal(ref, run.table)


def test_fault_demoted_replay_identical(monkeypatch):
    """Guaranteed-crash fault plan: every admitted group demotes to
    pushback replay — the tensor residual still matches the clean run."""
    from repro.core.faults import FaultPlan, RetryPolicy
    q = compile_query("Q6")
    clean = engine.run_query(q, CAT, CFG)
    cfg = engine.EngineConfig(
        mode="eager", residual="tensor",
        faults=FaultPlan.from_spec("pushdown.crash:1.0", seed=3),
        retry=RetryPolicy(sleep_scale=0.0))
    engine.run_query(q, CAT, cfg)                        # observe
    run = engine.run_query(q, CAT, cfg)
    assert run.recovery is not None and run.recovery["n_demoted"] > 0
    assert run.residual_backend == "tensor"
    assert engine.results_equal(clean.result, run.result)


def test_stream_on_process_tier_jits_residuals():
    """The served path: ``run_stream`` with storage-worker processes and
    the tensor residual. Workers are spawned pinned off the accelerator
    while this process holds JAX; the second stream's residuals run the
    jitted stages, and every answer matches the interpreter."""
    from repro.distributed import workers
    qs = [compile_query(q) for q in ("Q1", "Q5", "Q14")]
    cfg = engine.EngineConfig(residual="tensor", storage_tier="process")
    try:
        for _ in range(2):                       # observe, then jitted
            run = runtime.run_stream(
                [runtime.StreamQuery(q, arrival=0.0) for q in qs], CAT, cfg,
                time_scale=0.0)
    finally:
        workers.close_all_pools()
    for q in qs:
        want = engine.run_query(q, CAT, CFG).result
        assert engine.results_equal(want, run.results[q.qid]), q.qid
        info = run.per_query[q.qid]["residual_jit"]
        assert info["platforms"] == ("cpu",) and not info["fell_back"], q.qid


# ------------------------------------------------- engine accounting/auto
def test_queryrun_jit_accounting():
    q = compile_query("Q14")
    cfg = engine.EngineConfig(mode="eager", residual="tensor")
    r1 = engine.run_query(q, CAT, cfg)
    r2 = engine.run_query(q, CAT, cfg)
    r3 = engine.run_query(q, CAT, cfg)
    assert r1.residual_jit["observed"] is True
    assert r2.residual_jit["misses"] == r2.residual_jit["n_stages"]
    assert r3.residual_jit["hits"] == r3.residual_jit["n_stages"]
    assert r3.residual_jit["misses"] == 0
    assert not r3.residual_jit["fell_back"]
    assert r1.residual_jit["platforms"] == ()        # observe: host only
    assert r3.residual_jit["platforms"] == ("cpu",)


def test_auto_mode_threshold(monkeypatch):
    """auto = tensor at/above the crossover, interpreter below; the env
    override feeds the same knob the calibration would."""
    q = compile_query("Q6")
    monkeypatch.setattr(tensorize, "_AUTO_THRESHOLD", None)
    monkeypatch.setenv("REPRO_RESIDUAL_THRESHOLD", "1")
    r_hi = engine.run_query(
        q, CAT, engine.EngineConfig(mode="eager", residual="auto"))
    assert r_hi.residual_backend == "tensor"
    monkeypatch.setattr(tensorize, "_AUTO_THRESHOLD", None)
    monkeypatch.setenv("REPRO_RESIDUAL_THRESHOLD", str(1 << 40))
    r_lo = engine.run_query(
        q, CAT, engine.EngineConfig(mode="eager", residual="auto"))
    assert r_lo.residual_backend == "interpreter"
    assert engine.results_equal(r_hi.result, r_lo.result)
    monkeypatch.setattr(tensorize, "_AUTO_THRESHOLD", None)


def test_calibration_returns_usable_threshold(monkeypatch):
    """The measured crossover is a positive row count (or inf when the
    tensor backend never wins — auto then stays on the oracle), and
    REPRO_NO_CALIBRATE pins the documented default."""
    th = tensorize.calibrate_residual_threshold(sizes=(512, 2_048),
                                                repeats=1)
    assert th > 0
    monkeypatch.setattr(tensorize, "_AUTO_THRESHOLD", None)
    monkeypatch.delenv("REPRO_RESIDUAL_THRESHOLD", raising=False)
    monkeypatch.setenv("REPRO_NO_CALIBRATE", "1")
    assert tensorize.auto_threshold() == tensorize.DEFAULT_RESIDUAL_THRESHOLD
    monkeypatch.setattr(tensorize, "_AUTO_THRESHOLD", None)


def test_unknown_backend_rejected():
    q = compile_query("Q6")
    with pytest.raises(ValueError, match="residual backend"):
        engine.run_query(q, CAT,
                         engine.EngineConfig(mode="eager", residual="bogus"))


def test_seed_queries_without_residual_fall_through():
    """Hand-built seed queries carry no residual IR: the tensor backend
    must transparently run their compute closure."""
    from repro.queryproc import queries as Q
    q = Q.build_query_legacy("Q6")
    assert q.residual is None
    r = engine.run_query(q, CAT,
                         engine.EngineConfig(mode="eager", residual="tensor"))
    ref = engine.run_query(q, CAT, CFG)
    assert r.residual_backend == "interpreter"
    assert engine.results_equal(r.result, ref.result)


# ------------------------------------------------ specialization machinery
def _agg_residual():
    return ir.Aggregate(ir.Merged("t"), ("k",), (("s", "sum", "v"),))


def _tab(keys, vals=None):
    keys = np.asarray(keys, dtype=np.int64)
    vals = (np.ones(len(keys)) if vals is None
            else np.asarray(vals, dtype=np.float64))
    return ColumnTable({"k": keys, "v": vals})


@contextlib.contextmanager
def _replay_in_one_fallback_span(monkeypatch, owner, name, qid, reason,
                                 respec):
    """Trace the block, which trips one lowering guard, and check that
    ``owner.name`` (the host replay) ran once, inside the one
    ``residual_fallback`` span, whose attrs give the guard's reason."""
    from repro.obs.trace import tracing
    fn, calls = getattr(owner, name), []

    def timed(*a, **kw):
        t0 = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            calls.append((t0, time.perf_counter()))

    monkeypatch.setattr(owner, name, timed)
    with tracing() as tr:
        yield
    (fb,) = tr.find("residual_fallback")
    assert fb.attrs == {"qid": qid, "reason": reason, "respec": respec}
    ((t0, t1),) = calls
    assert tr.t0 + fb.t0 <= t0 and t1 <= tr.t0 + fb.t0 + fb.dur


def test_respecialize_on_domain_growth(monkeypatch):
    """Keys outside the observed domain trip the in-trace guard: that run
    falls back (still correct) and the artifact respecializes (gen bump)
    inside one ``residual_fallback`` span; the next run jits cleanly over
    the widened bounds."""
    res = _agg_residual()
    small = {"t": _tab(np.arange(64) % 4)}
    big = {"t": _tab(np.arange(64) % 4 + 100)}       # disjoint key range
    tensorize.execute(res, small)                    # observe on small
    art = tensorize._artifact(res)
    assert art.gen == 0
    ok = tensorize.execute(res, small)
    assert not ok.fell_back
    with _replay_in_one_fallback_span(
            monkeypatch, tensorize, "_respecialize", "Qa",
            "aggregate keys left the observed domain", respec=True):
        r_fb = tensorize.execute(res, big, "Qa")     # oob -> guard trips
    assert r_fb.fell_back
    assert engine.results_equal(interpreter.run(res, big), r_fb.table)
    assert art.gen == 1 and art.respecs == 1
    r_ok = tensorize.execute(res, big)               # widened spec jits
    assert not r_ok.fell_back and not art.disabled
    assert engine.results_equal(interpreter.run(res, big), r_ok.table)


def test_disabled_artifact_replays_in_a_fallback_span(monkeypatch, metrics):
    """Past the respecialization cap the artifact settles on the oracle:
    every later call counts one ``residual.fallbacks`` and replays inside
    its own ``residual_fallback`` span, reason "disabled"."""
    monkeypatch.setattr(tensorize, "_RESPEC_CAP", 1)
    res = _agg_residual()
    tabs = [{"t": _tab(np.arange(64) % 4 + 100 * k)} for k in range(3)]
    tensorize.execute(res, tabs[0])                  # observe
    art = tensorize._artifact(res)
    assert tensorize.execute(res, tabs[1]).fell_back     # respec 1
    assert not art.disabled
    assert tensorize.execute(res, tabs[2]).fell_back     # past the cap
    assert art.disabled
    for t in tabs:
        before = metrics.counter("residual.fallbacks").value
        with _replay_in_one_fallback_span(
                monkeypatch, interpreter, "run", "Qd", "disabled",
                respec=False):
            run = tensorize.execute(res, t, "Qd")
        assert run.fell_back
        assert metrics.counter("residual.fallbacks").value == before + 1
        assert engine.results_equal(interpreter.run(res, t), run.table)


@pytest.mark.parametrize("dtype,spec_len", [(np.int64, 3), (np.float64, 1)])
def test_huge_domain_aggregate_sorts(dtype, spec_len):
    """Group keys beyond the code-domain cap sort: integral keys as one
    packed code over their observed bounds (keys leaving them
    respecialize, as on the code path), other keys by lexsort. Both
    match the interpreter."""
    rng = np.random.default_rng(3)
    res = ir.Aggregate(ir.Merged("t"), ("a", "b"),
                       (("s", "sum", "v"), ("c", "count", "v")))

    def tab(lo):
        return {"t": ColumnTable({
            "a": rng.integers(lo, lo + 4_000, 3_000).astype(dtype),
            "b": rng.integers(0, 1_000, 3_000).astype(dtype),
            "v": rng.normal(size=3_000)})}

    first, wider = tab(0), tab(10_000)
    tensorize.execute(res, first)                    # observe
    art = tensorize._artifact(res)
    assert len(art.obs["agg"][id(res)]) == spec_len
    for merged in (first, wider, wider):
        run = tensorize.execute(res, merged)
        assert engine.results_equal(interpreter.run(res, merged), run.table)
    assert not run.fell_back
    assert art.gen == (1 if spec_len == 3 else 0)


def _jit_keyed_agg(node, spec):
    """One keyed aggregate's lowering, jitted on explicit padded columns
    and a validity mask. The jitted function returns the output columns,
    their validity mask and the in-trace respec flag; ``ways`` collects
    the lowering the trace chose."""
    import jax
    import jax.numpy as jnp
    art = types.SimpleNamespace(obs={"agg": {id(node): spec}})
    ways = []

    def fn(cols, valid):
        ctx = {"art": art, "respec": [], "agg_ways": ways}
        mt = tensorize._lower_aggregate(node, tensorize._MT(cols, valid),
                                        ctx)
        return mt.cols, mt.valid, jnp.any(jnp.stack(ctx["respec"]))

    return jax.jit(fn), ways


_CAP = tensorize._AGG_DENSE_CAP


@pytest.mark.parametrize("fn", ["count", "sum", "mean", "min", "max"])
@pytest.mark.parametrize("n", [16, 1 << 20])
@pytest.mark.parametrize("D", [1, 14, _CAP, _CAP + 1])
def test_code_aggregate_matches_interpreter(D, n, fn):
    """A keyed aggregate over a code domain of D keys reduces densely up
    to the cap, with no scatter in its program, and scatters above it.
    Either way, over an int and a float column with empty groups (odd
    codes never occur), it returns the interpreter's table; with every
    row invalid it returns no group; a valid key outside the observed
    domain raises the respec flag, and an invalid one does not."""
    import jax
    rng = np.random.default_rng(D * 31 + n)
    node = ir.Aggregate(ir.Merged("t"), ("k",),
                        (("i", fn, "iv"), ("f", fn, "fv")))
    f, ways = _jit_keyed_agg(node, ("code", (5,), (D,)))
    cols = {"k": 5 + 2 * rng.integers(0, (D + 1) // 2, n),
            "iv": rng.integers(-1000, 1000, n),
            "fv": rng.normal(size=n)}
    valid = rng.random(n) < 0.7
    valid[:2] = True
    with jax.enable_x64(True):
        text = f.lower(cols, valid).as_text()
        out, ovalid, respec = f(cols, valid)
        got = tensorize._unpad({"cols": out, "valid": ovalid})
        ref = interpreter.run(node, {"t": ColumnTable(cols).filter(valid)})
        assert engine.results_equal(ref, got) and not respec
        assert len(got) == len(np.unique(cols["k"][valid]))

        _, none_valid, respec = f(cols, np.zeros(n, bool))
        assert not np.asarray(none_valid).any() and not respec

        oob = dict(cols, k=cols["k"].copy())
        oob["k"][:2] = (4, 5)                        # below the domain
        assert bool(f(oob, valid)[2])
        oob["k"][:2] = (5, 5 + D)                    # above it
        assert bool(f(oob, valid)[2])
        assert not bool(f(oob, valid & (np.arange(n) != 1))[2])
    assert ways == ["dense" if D <= _CAP else "scatter"]
    assert ("scatter" in text) == (D > _CAP)


def test_shape_buckets_share_jitted_programs():
    """Row counts in the same pow-2 bucket reuse the compiled program;
    crossing a bucket boundary compiles once more, results identical."""
    res = _agg_residual()
    m900 = {"t": _tab(np.arange(900) % 8)}
    m1000 = {"t": _tab(np.arange(1000) % 8)}
    m1500 = {"t": _tab(np.arange(1500) % 8)}
    tensorize.execute(res, m900)                     # observe
    r1 = tensorize.execute(res, m900)                # 1024-bucket miss
    assert r1.jit_misses == 1
    r2 = tensorize.execute(res, m1000)               # same bucket: hit
    assert r2.jit_hits == 1 and r2.jit_misses == 0
    r3 = tensorize.execute(res, m1500)               # 2048-bucket: miss
    assert r3.jit_misses == 1
    for m in (m900, m1000, m1500):
        got = tensorize.execute(res, m)
        assert engine.results_equal(interpreter.run(res, m), got.table)
        assert got.jit_hits == 1


def test_join_duplicate_right_keys_falls_back(monkeypatch):
    """The dense-LUT probe requires unique build keys; a many-to-many
    right side must fall back to the interpreter with the same table.
    Traced, the interpreter's replay runs inside one
    ``residual_fallback`` span that gives the guard's reason."""
    res = ir.Join(ir.Merged("l"), ir.Merged("r"), "k", "rk")
    merged = {"l": ColumnTable({"k": np.asarray([1, 2, 3]),
                                "x": np.asarray([1.0, 2.0, 3.0])}),
              "r": ColumnTable({"rk": np.asarray([2, 2, 3]),
                                "y": np.asarray([10.0, 20.0, 30.0])})}
    ref = interpreter.run(res, merged)
    tensorize.execute(res, merged)                   # observe
    with _replay_in_one_fallback_span(
            monkeypatch, interpreter, "run", "Qj",
            "duplicate right join keys (m:n)", respec=False):
        run = tensorize.execute(res, merged, "Qj")
    assert run.fell_back
    assert engine.results_equal(ref, run.table)


def test_join_non_integer_keys_use_sorted_probe():
    """Float keys cannot index a dense LUT — the join must still jit via
    the in-trace sorted-probe path, not fall back."""
    res = ir.Join(ir.Merged("l"), ir.Merged("r"), "k", "rk")
    merged = {"l": ColumnTable({"k": np.asarray([1.5, 2.5, 3.5, 9.0]),
                                "x": np.asarray([1.0, 2.0, 3.0, 4.0])}),
              "r": ColumnTable({"rk": np.asarray([2.5, 3.5, 7.0]),
                                "y": np.asarray([10.0, 20.0, 30.0])})}
    ref = interpreter.run(res, merged)
    tensorize.execute(res, merged)                   # observe
    run = tensorize.execute(res, merged)
    assert not run.fell_back
    assert engine.results_equal(ref, run.table)


def test_empty_build_side():
    """An empty right table yields an empty (but well-formed) probe."""
    res = ir.SemiJoin(ir.Merged("l"), ir.Merged("r"), "k", "rk")
    merged = {"l": _tab([1, 2, 3]),
              "r": ColumnTable({"rk": np.asarray([], dtype=np.int64)})}
    ref = interpreter.run(res, merged)
    tensorize.execute(res, merged)                   # observe
    run = tensorize.execute(res, merged)
    assert len(run.table) == 0
    assert engine.results_equal(ref, run.table)


# ------------------------------------------------ persistent compile cache
@pytest.mark.parametrize("backend,outside", [("cpu", False), ("tpu", False),
                                             ("tpu", True)])
def test_compile_cache_placement(monkeypatch, tmp_path, backend, outside):
    """On an accelerator the cache goes where ``JAX_COMPILATION_CACHE_DIR``
    (read by JAX into ``jax_compilation_cache_dir``) says, else to the
    fixed checkout directory, with thresholds that keep every residual
    stage; on the CPU backend nothing is changed."""
    import jax
    from repro import jaxcache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    monkeypatch.setattr(jaxcache, "_DONE", False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    try:
        if outside:
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        path = jaxcache.enable_compile_cache()
        after = {n: getattr(jax.config, n) for n in names}
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
    if backend == "cpu":
        assert after == before and path == before[names[0]]
    else:
        assert path == (str(tmp_path) if outside
                        else jaxcache.CHECKOUT_CACHE_DIR)
        assert path == after["jax_compilation_cache_dir"]
        assert after["jax_persistent_cache_min_compile_time_secs"] == 0
        assert after["jax_persistent_cache_min_entry_size_bytes"] == -1


# --------------------------------------------- expression twin equivalence
def test_compile_expr_jnp_matches_numpy():
    import jax
    rng = np.random.default_rng(11)
    cols = {"a": rng.integers(0, 50, 400).astype(np.int64),
            "b": rng.normal(size=400),
            "c": rng.integers(0, 5, 400).astype(np.int64)}
    exprs = [
        Col("a") < 25,
        (Col("a") >= 10) & (Col("b") <= 0.3),
        (Col("b") > Col("b")) | Col("c").eq(2),
        Col("c").isin((1, 3, 4)) & (Col("a") > 5),
        (Col("a") <= Col("a")) & Col("c").isin((0,)),
    ]
    with jax.enable_x64(True):
        for e in exprs:
            want = ex.compile_expr(e)(cols)
            jf = jax.jit(compile_expr_jnp(e))
            got = np.asarray(jf({k: v for k, v in cols.items()}))
            assert np.array_equal(want, got), e
