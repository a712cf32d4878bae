"""End-to-end query engine over the disaggregated storage layer.

For one query the engine:

1. plans per-partition pushdown requests (one per partition of every
   scanned table — the paper's request granularity),
2. runs the Arbitrator + fluid simulator to obtain the pushdown/pushback
   decisions and the simulated timeline (the timeline is the paper's
   measured quantity — the container has no real 16-core storage node),
3. *really executes* the decision split (``core.runtime``): pushdown
   requests run storage-side through the fused batched executor
   (``core.executor``; the seed's per-partition loop stays as the
   ``executor="reference"`` oracle), pushed-back requests ship the raw
   accessed-column projection and the compute layer replays the same
   compiled plan — merged byte-identically for any decision vector, so
   correctness is independent of the scheduling mode while the bytes
   really flow where the Arbitrator sent them,
4. charges the non-pushable portion (joins/final aggs) to the compute
   layer's bandwidth, and reconciles real shipped bytes against the
   simulator's ``net_bytes``.

Modes: no_pushdown / eager / adaptive / adaptive_pa (§6.2 baselines).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import optimum, runtime
from repro.core.arbitrator import PUSHBACK, PUSHDOWN, MeasuredLoad
from repro.obs import trace as obs_trace
from repro.obs.metrics import get_metrics
from repro.core.cost import (CardinalityCorrector, RequestCost,
                             StorageResources)
from repro.core.executor import (EXECUTOR_BATCHED, EXECUTOR_REFERENCE,
                                 compile_push_plan)
from repro.core.plan import PushPlan, execute_push_plan, plan_signature
from repro.core.simulator import (MODE_ADAPTIVE, MODE_ADAPTIVE_PA, MODE_EAGER,
                                  MODE_NO_PUSHDOWN, SimRequest, SimResult,
                                  simulate)
from repro.queryproc.queries import Query
from repro.queryproc.table import ColumnTable
from repro.storage.catalog import Catalog, Partition

MODES = (MODE_NO_PUSHDOWN, MODE_EAGER, MODE_ADAPTIVE, MODE_ADAPTIVE_PA)

# storage tiers (EngineConfig.storage_tier): where the storage side of a
# split really runs.
#   inproc  — partitions execute in this process (the oracle; the seed's
#             behavior, byte-for-byte)
#   process — one real storage-worker process per catalog node
#             (distributed.workers.WorkerPool): plans dispatch over a
#             length-prefixed wire codec, pushback ships real serialized
#             bytes, workers publish live load signals, and worker death
#             flows through retry -> demote recovery. Results are
#             byte-identical across tiers for any decision vector and
#             fault schedule (docs/distributed.md).
STORAGE_INPROC = "inproc"
STORAGE_PROCESS = "process"
STORAGE_TIERS = (STORAGE_INPROC, STORAGE_PROCESS)


def resolve_tier(cfg, catalog: Catalog):
    """The worker pool a config's storage tier routes through, or ``None``
    for the in-process oracle. An explicit ``cfg.worker_pool`` (a
    ``distributed.workers.WorkerPool``, e.g. a test's own pool with a
    pinned kill schedule) wins over the named tier; otherwise
    ``storage_tier="process"`` resolves to the shared per-catalog pool
    (``workers.pool_for``), sized by the config's ``pd_slots``."""
    pool = getattr(cfg, "worker_pool", None)
    if pool is not None:
        return pool
    tier = getattr(cfg, "storage_tier", STORAGE_INPROC)
    if tier in (None, STORAGE_INPROC):
        return None
    if tier != STORAGE_PROCESS:
        raise ValueError(f"unknown storage_tier {tier!r}; "
                         f"expected one of {STORAGE_TIERS}")
    from repro.distributed.workers import pool_for  # lazy: keeps the
    #   multiprocessing machinery off every in-process import path
    return pool_for(catalog, pd_slots=cfg.res.pd_slots)


@dataclasses.dataclass
class EngineConfig:
    res: StorageResources = StorageResources()
    mode: str = MODE_ADAPTIVE
    compute_bw: float = 2.4e9   # compute-node operator bandwidth (16 vCPU)
    num_compute_nodes: int = 1
    executor: str = EXECUTOR_BATCHED  # real-execution path (results identical)
    # adaptive filter stage: estimated selectivity at/above which the batch
    # executor concatenates whole columns then masks once instead of
    # gathering survivors per partition. None = the import-time calibrated
    # crossover (core.executor.FILTER_GATHER_THRESHOLD). Bytes identical
    # either way — this knob is purely a performance override.
    filter_gather_threshold: Optional[float] = None
    # online s_out cardinality correction (core.cost.CardinalityCorrector):
    # when set, plan_requests rescales every request's estimated s_out by
    # the measured ratios and each executed run feeds its reconciliation
    # back — repeated runs converge the cost model (and through it the
    # Arbitrator's decisions) toward observed bytes. Purely an estimation
    # knob: results are byte-identical with or without it.
    corrector: Optional[CardinalityCorrector] = None
    # semantic pushed-result cache (core.result_cache.ResultCache): when
    # set, storage-side pushdown execution serves/fills it per partition,
    # and plan_requests probes it so warm partitions arbitrate with
    # compute_in=0 and the *known* result bytes as s_out — a cache hit
    # makes pushdown nearly free, flipping warm decisions toward pushdown.
    # Results stay byte-identical with or without it (the cache's core
    # contract; tests/test_cache.py).
    result_cache: Optional[object] = None
    # arbitrate over *measured* occupancy signals (the stream.* gauges
    # run_stream publishes every dispatch wave) instead of the fluid
    # model's own wait queues — see arbitrator.MeasuredLoad. Default ON
    # since the chaos soak (docs/faults.md) stress-tested the port; when a
    # node's gauges were never published the Arbitrator still falls back
    # to its fluid queue, and measured_feedback=False restores the pure
    # fluid reference behavior (regression-pinned in tests/test_cache.py).
    measured_feedback: bool = True
    # ---- fault tolerance (core.faults; docs/faults.md) -------------------
    # a FaultPlan makes every storage-execute boundary consult the
    # injection schedule; with one active (here or via REPRO_FAULT_SPEC)
    # execution retries under `retry` (default RetryPolicy) and demotes
    # exhausted pushdown groups to pushback — results stay byte-identical
    # under ANY schedule. All four default to None: fault-free configs run
    # the exact pre-fault code path.
    faults: Optional[object] = None       # faults.FaultPlan
    retry: Optional[object] = None        # faults.RetryPolicy
    hedge: Optional[object] = None        # faults.HedgePolicy (run_stream)
    breaker: Optional[object] = None      # faults.CircuitBreaker
    # storage tier (STORAGE_TIERS): "inproc" executes the storage side in
    # this process (the oracle); "process" dispatches it to real worker
    # processes over the wire (distributed.workers) — byte-identical
    # results, real transfer bytes, live worker load signals, and a real
    # process-failure fault domain. `worker_pool` (a WorkerPool) overrides
    # the named tier with an explicitly constructed pool.
    storage_tier: str = STORAGE_INPROC
    worker_pool: Optional[object] = None
    # residual backend (runtime.RESIDUALS): "interpreter" walks the
    # residual IR with the numpy oracle; "tensor" compiles it into fused
    # jax.jit programs (compiler.tensorize — jit-cached per input-shape
    # bucket); "auto" picks tensor at/above the calibrated row-count
    # crossover. Results are identical under every backend for every
    # mode and decision vector (tests/test_tensorize.py) — this knob is
    # purely a performance override, like filter_gather_threshold.
    residual: str = runtime.RESIDUAL_INTERPRETER


@dataclasses.dataclass
class PlannedRequest:
    req_id: int
    query_id: str
    table: str
    part: Partition
    plan: PushPlan
    cost: RequestCost      # as arbitrated (corrector-rescaled when active)
    s_out_raw: int = 0     # uncorrected s_out estimate — what the
    #                        corrector's feedback is measured against


@dataclasses.dataclass
class QueryRun:
    qid: str
    result: ColumnTable
    sim: SimResult
    t_pushable: float
    t_nonpushable: float
    requests: List[PlannedRequest]
    net_bytes: float            # simulated traffic (cost-model s_out/s_in)
    n_admitted: int
    n_pushed_back: int
    # real-execution accounting (core.runtime): bytes that actually crossed
    # the storage->compute boundary under the decision split, and the
    # reconciliation against the simulated figure above
    real_net_bytes: float = 0.0
    net_bytes_recon: Optional[Dict] = None
    outcomes: Optional[List[runtime.RequestOutcome]] = None
    # fault/recovery accounting (None on fault-free runs): n_demoted,
    # retries, faults_injected — reconciles exactly with the FaultPlan's
    # event ledger (tests/test_faults.py)
    recovery: Optional[Dict] = None
    # residual-backend accounting: which backend evaluated the residual
    # ("interpreter" | "tensor") and, on the tensor path, its jit-cache
    # hit/miss + fallback counters (None when the interpreter ran)
    residual_backend: str = "interpreter"
    residual_jit: Optional[Dict] = None

    @property
    def t_total(self) -> float:
        return self.t_pushable + self.t_nonpushable

    @property
    def cache_hits(self) -> int:
        """Pushdown partitions served by the pushed-result cache."""
        return sum(1 for o in (self.outcomes or ()) if o.cache)

    @property
    def n_demoted(self) -> int:
        """Admitted-pushdown requests recovered via pushback demotion."""
        return sum(1 for o in (self.outcomes or ()) if o.demoted)


def plan_requests(query: Query, catalog: Catalog, start_id: int = 0,
                  corrector: Optional[CardinalityCorrector] = None,
                  cache=None) -> List[PlannedRequest]:
    tr = obs_trace.get_tracer()
    with tr.span("plan_requests", qid=query.qid) as sp:
        out: List[PlannedRequest] = []
        rid = start_id
        n_warm = 0
        for table, plan in query.plans.items():
            # compile once per (query, table): the cost model's plan-level
            # invariants (accessed columns, selectivity closure) are shared
            # by every partition instead of recomputed ~160 times
            cplan = compile_push_plan(plan)
            sig = plan_signature(plan)
            for part in catalog.partitions_of(table):
                cost = cplan.estimate_cost(part)
                raw = cost.s_out
                hint = (cache.cost_hint(cplan, part)
                        if cache is not None else None)
                if hint is not None:
                    # warm partition: the pushed result already exists, so
                    # pushdown pays no storage CPU and ships a *known* byte
                    # count — the corrector is skipped (nothing estimated)
                    cost = dataclasses.replace(cost, compute_in=0,
                                               s_out=max(64, int(hint)))
                    n_warm += 1
                elif corrector is not None:
                    cost = corrector.correct(query.qid, table, sig, cost)
                out.append(PlannedRequest(rid, query.qid, table, part, plan,
                                          cost, s_out_raw=raw))
                rid += 1
        if tr.enabled:
            sp.set(n_requests=len(out), n_tables=len(query.plans),
                   est_s_out=sum(r.cost.s_out for r in out),
                   n_cache_warm=n_warm,
                   # the corrector's EWMA state *as used* for these
                   # estimates — decision-time provenance in the trace
                   corrector_state=(corrector.state(query.qid)
                                    if corrector is not None else None))
    return out


def _measured_of(cfg: EngineConfig) -> Optional[MeasuredLoad]:
    """The measured-signal port, when the config opts in (default off)."""
    return MeasuredLoad() if cfg.measured_feedback else None


def execute_requests(reqs: List[PlannedRequest],
                     executor: str = EXECUTOR_BATCHED,
                     filter_gather_threshold: Optional[float] = None
                     ) -> Dict[str, ColumnTable]:
    """Run every pushable sub-plan storage-side and merge in request order.

    ``executor="batched"`` stacks all partitions sharing one plan and runs a
    single fused, vectorized pass per (table, plan); ``"reference"`` is the
    seed's per-partition interpretive loop (the correctness oracle). Both
    return byte-identical merged tables for **any** request list
    (tests/test_executor.py): a table whose requests interleave several
    distinct plans merges its per-partition results back in original
    request order via ``execute_batch_parts``."""
    if executor == EXECUTOR_REFERENCE:
        by_table: Dict[str, List[ColumnTable]] = {}
        for r in reqs:
            res, _aux = execute_push_plan(r.plan, r.part.data)
            by_table.setdefault(r.table, []).append(res)
        return {t: ColumnTable.concat(parts) for t, parts in by_table.items()}
    by_table: Dict[str, List[PlannedRequest]] = {}
    for r in reqs:
        by_table.setdefault(r.table, []).append(r)
    if any(len({id(r.plan) for r in rs}) > 1 for rs in by_table.values()):
        # multi-plan tables: the request-order reassembly already lives in
        # the decision-split machinery — an empty decision vector routes
        # every request storage-side (pushdown is the default)
        return runtime.execute_split(reqs, {}, executor,
                                     filter_gather_threshold).merged
    # the common case: one plan per table — each table's requests form one
    # batch in request order, so the fused merged output needs no
    # reassembly
    return {table: compile_push_plan(rs[0].plan).execute_batch(
                [r.part.data for r in rs],
                threshold=filter_gather_threshold)
            for table, rs in by_table.items()}


def nonpushable_time(merged: Dict[str, ColumnTable], cfg: EngineConfig) -> float:
    """Joins/final aggregation at the compute layer: modeled as its input
    bytes over the compute-node operator bandwidth (stable across modes —
    the paper's Fig 9 shows exactly this invariance)."""
    b = sum(t.nbytes(stored=False) for t in merged.values())
    return b / (cfg.compute_bw * cfg.num_compute_nodes)


def _run_decided(query: Query, reqs: List[PlannedRequest], sim: SimResult,
                 cfg: EngineConfig, t_pushable: float, net_bytes: float,
                 bitmaps: Optional[Dict[int, np.ndarray]] = None,
                 tier=None) -> QueryRun:
    """Real execution routed by the simulator's decision vector
    (``core.runtime.execute_split``), plus the net-bytes reconciliation.
    ``bitmaps`` (req_id -> packed words) feeds apply_bitmap plans;
    ``tier`` (resolve_tier) routes the storage side through real worker
    processes."""
    tr = obs_trace.get_tracer()
    split = runtime.execute_split(reqs, sim.decisions(), cfg.executor,
                                  cfg.filter_gather_threshold,
                                  bitmaps=bitmaps, cache=cfg.result_cache,
                                  faults=cfg.faults, retry=cfg.retry,
                                  breaker=cfg.breaker, tier=tier)
    # the real split IS the simulated split — one decision vector, two
    # uses; under an active fault plan, admitted requests that exhausted
    # their retries were *demoted* to pushback (graceful degradation, the
    # recovery contract) and are accounted separately
    assert split.n_pushdown + split.n_demoted == sim.admitted(query.qid), \
        (query.qid, split.n_pushdown, split.n_demoted,
         sim.admitted(query.qid))
    if cfg.corrector is not None:
        # close the loop: measured pushdown bytes correct future estimates
        runtime.feed_corrector(cfg.corrector, query.qid, reqs,
                               split.outcomes)
    with tr.span("residual_compute", qid=query.qid,
                 backend=cfg.residual) as rsp:
        result, trun = runtime.run_residual(query, split.merged,
                                            cfg.residual)
        if tr.enabled and trun is not None:
            tr.amend(rsp, backend="tensor", jit_hits=trun.jit_hits,
                     jit_misses=trun.jit_misses, fell_back=trun.fell_back)
    t_np = nonpushable_time(split.merged, cfg)
    m = get_metrics()
    m.counter("engine.queries").inc()
    m.counter("engine.requests.pushdown").inc(split.n_pushdown)
    m.counter("engine.requests.pushback").inc(len(reqs) - split.n_pushdown)
    m.counter("engine.net_bytes.real").inc(split.real_net_bytes)
    n_hit = sum(1 for o in split.outcomes if o.cache)
    if n_hit:
        m.counter("engine.cache_hits").inc(n_hit)
    if split.n_demoted:
        m.counter("engine.requests.demoted").inc(split.n_demoted)
    recovery = None
    if split.n_demoted or split.retries or split.faults_injected:
        recovery = {"n_demoted": split.n_demoted,
                    "retries": split.retries,
                    "faults_injected": split.faults_injected}
    return QueryRun(
        qid=query.qid, result=result, sim=sim,
        t_pushable=t_pushable, t_nonpushable=t_np, requests=reqs,
        net_bytes=net_bytes,
        n_admitted=sim.admitted(query.qid),
        n_pushed_back=sim.pushed_back_by_query.get(query.qid, 0),
        real_net_bytes=split.real_net_bytes,
        net_bytes_recon=runtime.reconcile_net_bytes(sim, reqs, split),
        outcomes=split.outcomes, recovery=recovery,
        residual_backend=("tensor" if trun is not None else "interpreter"),
        residual_jit=runtime.residual_jit_info(trun))


def run_query(query: Query, catalog: Catalog, cfg: EngineConfig,
              requests: Optional[List[PlannedRequest]] = None,
              bitmaps: Optional[Dict[int, np.ndarray]] = None) -> QueryRun:
    tr = obs_trace.get_tracer()
    with tr.span("query", qid=query.qid, mode=cfg.mode) as qs:
        reqs = requests if requests is not None \
            else plan_requests(query, catalog, corrector=cfg.corrector,
                               cache=cfg.result_cache)
        sim_reqs = [SimRequest(r.req_id, r.part.node_id, query.qid, r.cost)
                    for r in reqs]
        sim = simulate(sim_reqs, cfg.res, cfg.mode,
                       measured=_measured_of(cfg), breaker=cfg.breaker)
        run = _run_decided(query, reqs, sim, cfg,
                           t_pushable=sim.makespan, net_bytes=sim.net_bytes,
                           bitmaps=bitmaps,
                           tier=resolve_tier(cfg, catalog))
        if tr.enabled:
            _set_query_attrs(qs, run)
    return run


def _set_query_attrs(qs, run: "QueryRun") -> None:
    """Roll the run's accounting up onto its ``query`` span."""
    recon = run.net_bytes_recon or {}
    qs.set(real_net_bytes=float(run.real_net_bytes),
           sim_net_bytes=float(run.net_bytes),
           n_pushdown=run.n_admitted, n_pushback=run.n_pushed_back,
           t_pushable=run.t_pushable, t_nonpushable=run.t_nonpushable,
           s_out_est_ratio=recon.get("s_out_estimate_ratio"),
           cache_hits=run.cache_hits,
           net_bytes_recon=recon)


def run_concurrent(queries: List[Query], catalog: Catalog, cfg: EngineConfig
                   ) -> Dict[str, QueryRun]:
    """Multiple queries submitted simultaneously (§6.2 PA-aware experiment).
    All requests share the storage nodes' wait queues and slots."""
    all_reqs: List[PlannedRequest] = []
    for q in queries:
        all_reqs.extend(plan_requests(q, catalog, start_id=len(all_reqs),
                                      corrector=cfg.corrector,
                                      cache=cfg.result_cache))
    sim_reqs = [SimRequest(r.req_id, r.part.node_id, r.query_id, r.cost)
                for r in all_reqs]
    sim = simulate(sim_reqs, cfg.res, cfg.mode,
                   measured=_measured_of(cfg), breaker=cfg.breaker)
    tr = obs_trace.get_tracer()
    tier = resolve_tier(cfg, catalog)
    out: Dict[str, QueryRun] = {}
    for q in queries:
        reqs = [r for r in all_reqs if r.query_id == q.qid]
        with tr.span("query", qid=q.qid, mode=cfg.mode,
                     concurrent=True) as qs:
            run = _run_decided(
                q, reqs, sim, cfg, t_pushable=sim.finish_by_query[q.qid],
                net_bytes=sim.net_bytes_by_query[q.qid], tier=tier)
            if tr.enabled:
                _set_query_attrs(qs, run)
        out[q.qid] = run
    return out


def compile_and_run(qid: str, catalog: Catalog, cfg: EngineConfig,
                    fact_selectivity: Optional[float] = None,
                    cost_based: bool = False) -> QueryRun:
    """Compiler front door: logical-plan IR -> amenability split -> run.
    Equivalent to ``run_query(compiler.compile_query(qid), ...)``.
    ``cost_based=True`` routes through ``compile_query_costed`` instead:
    the frontier cut is chosen by estimated cost over this catalog (and by
    the config's corrector, when one is set) — results are identical
    either way."""
    # deferred imports: the compiler imports core.plan/core.cost
    if cost_based:
        from repro.compiler import compile_query_costed
        cq = compile_query_costed(qid, catalog, res=cfg.res,
                                  corrector=cfg.corrector,
                                  fact_selectivity=fact_selectivity,
                                  compute_bw=cfg.compute_bw)
        return run_query(cq.query, catalog, cfg)
    from repro.compiler import compile_query
    return run_query(compile_query(qid, fact_selectivity), catalog, cfg)


# ------------------------------------------------------------ validation
def theoretical_split(query: Query, catalog: Catalog, res: StorageResources):
    """Discrete oracle split (§3.1) for the gap evaluation (Fig 7)."""
    reqs = plan_requests(query, catalog)
    return optimum.discrete_optimum([r.cost for r in reqs], res)


def results_equal(a: ColumnTable, b: ColumnTable, tol: float = 1e-6) -> bool:
    """Order-insensitive table equality: same *row multiset* up to float
    tolerance.

    Rows are aligned via one lexsort over ALL columns (exact columns
    leading, float columns last so a sub-tolerance jitter cannot flip the
    row order between the two tables), then compared row-wise. Sorting
    each column independently — the previous implementation — accepts
    tables with entirely different row sets whenever every column happens
    to hold the same value multiset (e.g. rows {(1,2),(2,1)} vs
    {(1,1),(2,2)}); tests/test_runtime.py pins the regression."""
    if set(a.columns) != set(b.columns) or len(a) != len(b):
        return False
    if len(a) == 0:
        return True
    cols = sorted(a.columns)
    is_float = {c: (np.asarray(a.cols[c]).dtype.kind in "fc"
                    or np.asarray(b.cols[c]).dtype.kind in "fc")
                for c in cols}
    # exact columns first in sort priority (lexsort: last key is primary)
    key_order = [c for c in cols if is_float[c]] + \
                [c for c in cols if not is_float[c]]

    def row_order(t: ColumnTable) -> np.ndarray:
        return np.lexsort(tuple(np.asarray(t.cols[c]) for c in key_order))

    ia, ib = row_order(a), row_order(b)
    for c in cols:
        x = np.asarray(a.cols[c])[ia]
        y = np.asarray(b.cols[c])[ib]
        if is_float[c]:
            if not np.allclose(x, y, rtol=tol, atol=tol):
                return False
        elif not np.array_equal(x, y):
            return False
    return True
