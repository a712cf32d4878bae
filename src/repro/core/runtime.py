"""Decision-faithful adaptive runtime: arbitration drives real execution.

The simulator/Arbitrator produce a per-request pushdown/pushback decision
vector (``SimResult.per_request``). Before this module, those decisions
only shaped the *simulated* timeline — ``engine.execute_requests`` ran
every partition through the storage-side batched executor regardless. Here
the decision vector routes the real bytes, exactly as the paper's adaptive
pushdown does:

- **pushdown** requests execute at the storage layer through the fused
  batched executor (``core.executor``), and ship only their *results*
  (plus any §4.2 aux by-products);
- **pushback** requests ship the raw accessed-column projection — the
  paper's ``S_in`` — and the *compute layer* replays the very same
  ``CompiledPushPlan`` over the shipped batch (including the shuffle /
  bitmap aux paths), so the work moves but the plan does not change.

The merged per-table results are **byte-identical to all-pushdown
execution for any decision vector**: per-partition outputs are
batch-composition-invariant (pinned by ``tests/test_executor.py``), and
``execute_split`` reassembles them in original request order. Real
execution is therefore correct under every engine mode
(no_pushdown / eager / adaptive / adaptive_pa).

Real net-bytes accounting rides along: pushdown requests are charged their
actual result bytes (vs the cost model's estimated ``s_out``), pushback
requests their stored accessed-column bytes (identical to the simulator's
``s_in`` — the estimate is exact on that path), and
``reconcile_net_bytes`` lines both up against ``SimResult.net_bytes``.

``run_stream`` is the concurrent wall-clock driver: arrival-timed
multi-query waves, per-node worker pools sized by the storage slot pools
(``pd_slots`` execution workers, ``pb_slots`` transfer workers per node, a
compute pool for pushback replay + final plan residuals), with dispatch
order taken live from the Arbitrator's decision callback. It feeds the
``benchmarks/adaptive.py`` real adaptive-vs-eager-vs-no-pushdown A/B.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from concurrent.futures import (FIRST_COMPLETED, Future, ThreadPoolExecutor,
                                TimeoutError as FutTimeout, wait as fut_wait)
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import faults as _faults
from repro.core.arbitrator import PUSHBACK, PUSHDOWN
from repro.core.cost import CardinalityCorrector
from repro.core.executor import (EXECUTOR_BATCHED, EXECUTOR_REFERENCE,
                                 CompiledPushPlan, compile_push_plan)
from repro.core.plan import execute_push_plan, plan_signature
from repro.obs import trace as obs_trace
from repro.obs.metrics import get_metrics
from repro.queryproc.table import ColumnTable

# residual backends (EngineConfig.residual): how the compute layer
# evaluates the post-pushdown residual plan over the merged tables.
#   interpreter — the numpy tree-walker (compiler.interpreter), the oracle
#   tensor      — fused jax.jit programs (compiler.tensorize), results
#                 identical, faster on residual-dominant queries
#   auto        — tensor iff the merged input is at or above the
#                 calibrated crossover (tensorize.auto_threshold)
RESIDUAL_INTERPRETER = "interpreter"
RESIDUAL_TENSOR = "tensor"
RESIDUAL_AUTO = "auto"
RESIDUALS = (RESIDUAL_INTERPRETER, RESIDUAL_TENSOR, RESIDUAL_AUTO)


def run_residual(query, merged: Dict[str, ColumnTable],
                 backend: str = RESIDUAL_INTERPRETER):
    """Evaluate ``query``'s residual over the merged per-table results.

    Returns ``(table, info)`` where ``info`` is ``None`` on the
    interpreter path and a ``tensorize.TensorRun`` (jit-cache hit/miss,
    fallback and observe accounting) on the tensor path. Queries with no
    attached residual IR (hand-built seed queries) always run their
    ``compute`` closure — the tensor backend needs the IR. Both backends
    produce identical tables for every query and decision vector
    (tests/test_tensorize.py)."""
    if backend is not None and backend not in RESIDUALS:
        raise ValueError(f"unknown residual backend {backend!r}; "
                         f"expected one of {RESIDUALS}")
    residual = getattr(query, "residual", None)
    if residual is None or backend in (None, RESIDUAL_INTERPRETER):
        return query.compute(merged), None
    from repro.compiler import tensorize  # lazy: keeps jax off cold paths
    if backend == RESIDUAL_AUTO:
        rows = sum(len(t) for t in merged.values())
        if rows < tensorize.auto_threshold():
            return query.compute(merged), None
    run = tensorize.execute(residual, merged, query.qid)
    return run.table, run


def residual_jit_info(trun) -> Optional[Dict]:
    """The tensor residual's per-run accounting as ``QueryRun.residual_jit``
    and ``StreamRun.per_query[...]["residual_jit"]`` report it (``None``
    when the interpreter ran): jit-cache hits/misses, whether the run was
    the observe pass or a designed fallback, and the platforms of the
    devices its jitted stages ran on."""
    if trun is None:
        return None
    return {"hits": trun.jit_hits, "misses": trun.jit_misses,
            "fell_back": trun.fell_back, "observed": trun.observed,
            "n_stages": trun.n_stages, "platforms": trun.platforms}


# --------------------------------------------------------- split execution
@dataclasses.dataclass
class RequestOutcome:
    """What one request really did: where it ran and what it shipped."""
    req_id: int
    table: str
    path: str            # PUSHDOWN | PUSHBACK
    rows_out: int        # plan-output rows for this partition
    shipped_bytes: int   # pushdown: actual result(+aux) bytes;
    #                      pushback: stored accessed-column bytes (s_in)
    replayed: bool       # True when the plan ran at the compute layer
    cache: Optional[str] = None  # "exact" | "containment" when the result
    #                              was served by the pushed-result cache
    # ---- fault/recovery accounting (core.faults; zero when no fault plan)
    attempts: int = 1    # storage-execute attempts (1 = clean first try)
    demoted: bool = False  # decided pushdown, exhausted retries, recovered
    #                        via pushback (path above reflects the demotion)
    hedged: bool = False   # a hedge duplicate won this group's race


@dataclasses.dataclass
class SplitExecution:
    """Merged tables + real-traffic accounting of one decision vector."""
    merged: Dict[str, ColumnTable]
    outcomes: List[RequestOutcome]   # original request order
    n_pushdown: int
    n_pushback: int
    pushdown_bytes: int              # actually shipped pushdown results
    pushback_bytes: int              # actually shipped raw projections
    # ---- recovery accounting (zero on fault-free runs)
    n_demoted: int = 0               # decided-pushdown requests recovered
    #                                  via pushback demotion
    retries: int = 0                 # backoff-retried attempts, all groups
    faults_injected: int = 0         # injected fault events hit by this run

    @property
    def real_net_bytes(self) -> int:
        return self.pushdown_bytes + self.pushback_bytes


def result_bytes(result: ColumnTable, aux: Dict) -> int:
    """Bytes a pushdown result really ships — same arithmetic as
    ``plan.actual_out_bytes`` (64-byte floor, packed bitmap rides along)
    without materializing column stats for every per-partition slice."""
    b = sum(int(v.nbytes) for v in result.cols.values()) if len(result) \
        else 64
    if "bitmap" in aux:
        b += int(aux["bitmap"].nbytes)
    return int(b)


def pushback_bytes(cplan: CompiledPushPlan, data: ColumnTable) -> int:
    """Stored bytes of the raw accessed-column projection — exactly the
    cost model's ``s_in`` (the pushback estimate is exact, not a guess)."""
    return int(data.nbytes([c for c in cplan.accessed if c in data.cols],
                           stored=True))


def _exec_group(cplan: CompiledPushPlan, sub, path: str, executor: str,
                threshold: Optional[float],
                bitmaps: Optional[Dict[int, np.ndarray]] = None,
                shipped: Optional[List[ColumnTable]] = None,
                cache=None, tier=None,
                parent: Optional[obs_trace.Span] = None
                ) -> List[Tuple[ColumnTable, Dict]]:
    """Execute one same-(table, plan, path) request group. Pushback groups
    run the same compiled plan over raw projections (``shipped`` lets the
    stream driver pass transfer-copied batches instead of in-place views).

    ``cache`` (a ``core.result_cache.ResultCache``) applies to the
    storage-side batched pushdown path only: pushback replays run at the
    compute layer over already-shipped bytes (nothing storage-side to
    save), and the per-partition reference stays the uncached oracle.

    ``tier`` (a ``distributed.workers.WorkerPool``) reroutes the storage
    side over the wire: pushdown dispatches the compiled plan to the
    partition-owning worker *process*; pushback fetches the raw
    accessed-column projection as real serialized bytes and replays the
    plan compute-side over the decoded tables — byte-identical to the
    in-process paths (the tier oracle contract, docs/distributed.md). A
    dead/overdue channel raises ``faults.WorkerFault``, which the
    recovery loop maps onto the retry -> demote machinery.
    """
    if tier is not None and shipped is None:
        if path == PUSHDOWN:
            return tier.execute_group(cplan, sub, executor, threshold,
                                      bitmaps=bitmaps, parent=parent)
        shipped = tier.fetch_projection(cplan, sub, parent=parent)
    if shipped is not None:
        tabs = shipped
    elif path == PUSHDOWN:
        tabs = [r.part.data for r in sub]
    else:
        tabs = [cplan.raw_projection(r.part.data) for r in sub]
    bms = [bitmaps[r.req_id] for r in sub] if bitmaps else None
    if executor == EXECUTOR_REFERENCE:
        return [execute_push_plan(cplan.plan, t,
                                  None if bms is None else bms[i])
                for i, t in enumerate(tabs)]
    cache_parts = ([r.part for r in sub]
                   if cache is not None and path == PUSHDOWN
                   and shipped is None else None)
    parts, aux = cplan.execute_batch_parts(
        tabs, bms, threshold,
        cache=cache if cache_parts is not None else None, parts=cache_parts)
    return list(zip(parts, aux))


def _exec_group_traced(cplan: CompiledPushPlan, sub, path: str,
                       executor: str, threshold: Optional[float],
                       bitmaps: Optional[Dict[int, np.ndarray]] = None,
                       shipped: Optional[List[ColumnTable]] = None,
                       parent: Optional[obs_trace.Span] = None,
                       node: Optional[int] = None,
                       cache=None, tier=None
                       ) -> Tuple[List[Tuple[ColumnTable, Dict]],
                                  obs_trace.Span]:
    """``_exec_group`` under a span: ``storage_execute`` for pushdown
    batches, ``compute_replay`` for pushed-back ones. Returns the (closed)
    span alongside the results so the caller can attach ``shipped_bytes``
    from the **same** per-request accounting it computes anyway
    (``result_bytes`` / ``pushback_bytes``) — traces reconcile with
    ``SplitExecution.real_net_bytes`` *exactly*, and the bytes are never
    computed twice."""
    tr = obs_trace.get_tracer()
    name = "storage_execute" if path == PUSHDOWN else "compute_replay"
    with tr.span(name, parent=parent, table=sub[0].table,
                 n_parts=len(sub), node=node) as sp:
        out = _exec_group(cplan, sub, path, executor, threshold,
                          bitmaps=bitmaps, shipped=shipped, cache=cache,
                          tier=tier, parent=sp)
        if tr.enabled:
            sp.set(rows_out=int(sum(len(res) for res, _ in out)),
                   signature=plan_signature(cplan.plan),
                   cache_hits=sum(1 for _res, a in out if a.get("cache")))
    return out, sp


@dataclasses.dataclass
class GroupRecovery:
    """What recovery did for one executed request group."""
    attempts: int = 1                 # executions tried (incl. the success)
    retries: int = 0                  # failed attempts that were retried
    injected: List[str] = dataclasses.field(default_factory=list)
    real_faults: List[str] = dataclasses.field(default_factory=list)
    #   WorkerFault kinds observed at the process-tier channel boundary
    #   (disjoint from ``injected`` — the pool's ``events`` ledger is the
    #   authoritative real-fault record the tests reconcile against)
    demoted: bool = False             # exhausted -> fallback execution ran
    charged_s: float = 0.0            # charged (virtual) seconds consumed


def _exec_group_recovered(cplan: CompiledPushPlan, sub, path: str,
                          executor: str, threshold: Optional[float],
                          faults: Optional["_faults.FaultPlan"],
                          retry: "_faults.RetryPolicy",
                          breaker: Optional["_faults.CircuitBreaker"] = None,
                          bitmaps: Optional[Dict[int, np.ndarray]] = None,
                          shipped: Optional[List[ColumnTable]] = None,
                          parent: Optional[obs_trace.Span] = None,
                          node: Optional[int] = None,
                          cache=None, salt: str = "", tier=None,
                          abort: Optional[threading.Event] = None
                          ) -> Tuple[List[Tuple[ColumnTable, Dict]],
                                     obs_trace.Span, GroupRecovery]:
    """``_exec_group_traced`` under the fault/recovery contract.

    Each attempt consults the ``FaultPlan`` (when one is active) at the
    storage-execute boundary. A ``straggler`` completes (late: the
    injected delay is both charged and really slept, scaled);
    ``crash``/``timeout``/``transient`` abort the attempt, charge the
    deadline budget their nominal detection cost, and retry after capped
    exponential backoff with deterministic jitter. On the process storage
    tier the same loop also absorbs **real** failures: a
    :class:`core.faults.WorkerFault` raised at the channel boundary
    (worker SIGKILL -> EOF, or an overdue request) is handled exactly like
    an injected fault of the same kind — charged, counted, retried — except
    that a real timeout already waited its detection time out on the wire,
    so nothing extra is slept. On exhaustion (attempts or charged budget):

    - ``retry.demote_on_exhaust`` (the contract): a pushdown group is
      **demoted to pushback** — ship the raw projection, replay the
      compiled plan compute-side, byte-identical by the PR-4 contract; an
      already-pushback group replays cleanly from the durable projection
      (``retry.local_replays``). The fallback execution is not re-injected
      and, on the process tier, runs **in-process from the parent's
      catalog copy** (``tier=None``): the recovery tier (durable store +
      local compute) is outside the storage fault model — which is what
      makes "never an error" a guarantee rather than a probability.
    - otherwise: raise :class:`core.faults.FaultExhausted` — the
      fail-to-error baseline the chaos benchmark compares against.

    ``abort`` is the hedge loser's cancellation token: a set token raises
    :class:`core.faults.HedgeAborted` at the next attempt boundary (and
    before the demote fallback), so a lost race cannot keep charging the
    fault ledger, the byte counters, or the calibration samples.

    Every outcome feeds the circuit breaker (when given) and the
    ``faults.node<N>.<path>.failures``/``.successes`` counters — the same
    live per-node signals ``MeasuredLoad``-style pollers consume.
    """
    m = get_metrics()
    tr = obs_trace.get_tracer()
    node_id = node if node is not None else sub[0].part.node_id
    table = sub[0].table
    key = f"{min(r.req_id for r in sub)}x{len(sub)}"
    rec = GroupRecovery()
    budget = retry.deadline_s
    scale = retry.real_scale()
    attempt = 1
    while True:
        if abort is not None and abort.is_set():
            raise _faults.HedgeAborted(node_id, path, table)
        action = faults.draw(node_id, path, table, key, attempt, salt) \
            if faults is not None else None
        kind = real = None
        if action is None or action.kind == _faults.FAULT_STRAGGLER:
            if action is not None:
                m.counter(f"faults.{_faults.FAULT_STRAGGLER}").inc()
                rec.injected.append(_faults.FAULT_STRAGGLER)
                delay = action.param if action.param is not None \
                    else retry.attempt_timeout_s
                rec.charged_s += delay
                if tr.enabled:
                    tr.event("fault_injected", parent=parent,
                             kind=_faults.FAULT_STRAGGLER, node=node_id,
                             table=table, path=path, attempt=attempt,
                             delay_s=delay)
                if delay * scale > 0:
                    time.sleep(delay * scale)
            try:
                out, sp = _exec_group_traced(cplan, sub, path, executor,
                                             threshold, bitmaps=bitmaps,
                                             shipped=shipped, parent=parent,
                                             node=node_id, cache=cache,
                                             tier=tier)
            except _faults.WorkerFault as wf:
                kind, real = wf.kind, True
                rec.real_faults.append(kind)
            else:
                rec.attempts = attempt
                m.counter(f"faults.node{node_id}.{path}.successes").inc()
                if breaker is not None:
                    breaker.record_success(node_id, path)
                return out, sp, rec
        else:
            kind = action.kind
            rec.injected.append(kind)
        m.counter(f"faults.{kind}").inc()
        m.counter(f"faults.node{node_id}.{path}.failures").inc()
        if breaker is not None:
            breaker.record_failure(node_id, path)
        if tr.enabled:
            tr.event("worker_fault" if real else "fault_injected",
                     parent=parent, kind=kind, node=node_id, table=table,
                     path=path, attempt=attempt)
        charge = retry.charge(kind)
        rec.charged_s += charge
        budget -= charge
        if not real and kind == _faults.FAULT_TIMEOUT and charge * scale > 0:
            time.sleep(charge * scale)  # an *injected* timeout really waits
            #   the attempt out; a real one already did, on the wire
        if attempt < retry.max_attempts and budget > 0:
            u = faults.jitter(node_id, path, table, key, attempt) \
                if faults is not None else 0.5
            back = retry.backoff_s(attempt, u)
            rec.charged_s += back
            budget -= back
            if budget > 0:
                rec.retries += 1
                m.counter("retry.attempts").inc()
                if tr.enabled:
                    tr.event("retry", parent=parent, attempt=attempt + 1,
                             node=node_id, table=table, backoff_s=back,
                             budget_s=budget)
                if back * scale > 0:
                    time.sleep(back * scale)
                attempt += 1
                continue
        # exhausted: retries or charged deadline budget ran out
        rec.attempts = attempt
        if not retry.demote_on_exhaust:
            m.counter("retry.exhausted").inc()
            raise _faults.FaultExhausted(kind, node_id, path, table, attempt)
        if abort is not None and abort.is_set():
            raise _faults.HedgeAborted(node_id, path, table)
        rec.demoted = True
        m.counter("retry.demotions" if path == PUSHDOWN
                  else "retry.local_replays").inc()
        with tr.span("demote", parent=parent, node=node_id, table=table,
                     from_path=path, attempts=attempt, kind=kind):
            out, sp = _exec_group_traced(cplan, sub, PUSHBACK, executor,
                                         threshold, bitmaps=bitmaps,
                                         shipped=shipped, parent=parent,
                                         node=node_id, cache=cache)
        if breaker is not None and path == PUSHDOWN:
            # the fallback succeeded on the *other* path
            breaker.record_success(node_id, PUSHBACK)
        return out, sp, rec


def execute_split(reqs, decisions: Dict[int, str],
                  executor: str = EXECUTOR_BATCHED,
                  threshold: Optional[float] = None,
                  bitmaps: Optional[Dict[int, np.ndarray]] = None,
                  cache=None, faults=None, retry=None,
                  breaker=None, tier=None) -> SplitExecution:
    """Route every request down its decided path and merge.

    ``reqs`` is a list of ``engine.PlannedRequest``; ``decisions`` maps
    ``req_id -> PUSHDOWN | PUSHBACK`` (missing ids default to pushdown).
    Requests sharing a (table, plan, path) execute as one fused batch; the
    per-table merge concatenates per-partition results in **original
    request order**, so the merged tables are byte-identical to
    all-pushdown execution for any decision vector.

    ``faults``/``retry``/``breaker`` (core.faults): with a ``FaultPlan``
    active — passed in, or ambient via ``REPRO_FAULT_SPEC`` — every group
    executes through the retry/deadline/demote recovery loop
    (``_exec_group_recovered``), grouped additionally **per storage node**
    so injection scopes match the fleet topology, and the split carries
    the recovery accounting (``n_demoted``/``retries``/``faults_injected``).
    Byte-identity holds under ANY fault schedule: demotion is just the
    pushback path, and the merge order never changes. Without a plan this
    function is byte-for-byte the fault-free PR-4 code path.

    ``tier`` (``distributed.workers.WorkerPool``): route the storage side
    through real worker processes. Grouping always splits per node (each
    worker owns its node's partitions), execution always runs through the
    recovery loop (real channel faults must flow retry -> demote even
    with no injected plan; the retry policy is auto-armed), and the
    result cache is bypassed (the workers own the storage side — a
    parent-side cache would fake locality the wire no longer has).
    """
    if faults is None:
        faults = _faults.env_plan()
    if faults is not None or tier is not None:
        retry = retry if retry is not None else _faults.RetryPolicy()
    if tier is not None:
        cache = None
    tr = obs_trace.get_tracer()
    with tr.span("execute_split", n_requests=len(reqs)) as es:
        per_req: Dict[int, ColumnTable] = {}
        out_by_id: Dict[int, RequestOutcome] = {}
        n_pd = n_pb = n_dem = retries = injected = 0
        pd_bytes = pb_bytes = 0
        groups: Dict[Tuple, List] = {}
        recovered = faults is not None or tier is not None
        for r in reqs:
            # with a fault plan or a process tier, groups split per node:
            # injection, recovery, and partition ownership are all
            # per-(node, path) — the fleet's failure unit
            gkey = (r.table, id(r.plan)) if not recovered \
                else (r.table, id(r.plan), r.part.node_id)
            groups.setdefault(gkey, []).append(r)
        for _gkey, rs in groups.items():
            cplan = compile_push_plan(rs[0].plan)
            for path in (PUSHDOWN, PUSHBACK):
                sub = [r for r in rs
                       if decisions.get(r.req_id, PUSHDOWN) == path]
                if not sub:
                    continue
                if not recovered:
                    out, gsp = _exec_group_traced(cplan, sub, path, executor,
                                                  threshold, bitmaps=bitmaps,
                                                  cache=cache)
                    rec = None
                    eff_path = path
                else:
                    out, gsp, rec = _exec_group_recovered(
                        cplan, sub, path, executor, threshold, faults,
                        retry, breaker=breaker, bitmaps=bitmaps, cache=cache,
                        tier=tier)
                    retries += rec.retries
                    injected += len(rec.injected)
                    eff_path = PUSHBACK if rec.demoted else path
                demoted = rec is not None and rec.demoted \
                    and path == PUSHDOWN
                g_bytes = 0
                for r, (res, aux) in zip(sub, out):
                    per_req[r.req_id] = res
                    if eff_path == PUSHDOWN:
                        b = result_bytes(res, aux)
                        pd_bytes += b
                        n_pd += 1
                    else:
                        b = pushback_bytes(cplan, r.part.data)
                        pb_bytes += b
                        n_pb += 1
                        if demoted:
                            n_dem += 1
                    g_bytes += b
                    out_by_id[r.req_id] = RequestOutcome(
                        r.req_id, r.table, eff_path, len(res), b,
                        replayed=(eff_path == PUSHBACK),
                        cache=aux.get("cache"),
                        attempts=rec.attempts if rec is not None else 1,
                        demoted=demoted)
                tr.amend(gsp, shipped_bytes=int(g_bytes))
        by_table: Dict[str, List[ColumnTable]] = {}
        for r in reqs:
            by_table.setdefault(r.table, []).append(per_req[r.req_id])
        with tr.span("merge", tables=sorted(by_table)):
            merged = {t: ColumnTable.concat(parts)
                      for t, parts in by_table.items()}
        outs = [out_by_id[r.req_id] for r in reqs]
        if tr.enabled:
            # the RequestOutcome list rides along by reference; exporters
            # coerce dataclasses to dicts at export time
            es.set(n_pushdown=n_pd, n_pushback=n_pb,
                   pushdown_bytes=int(pd_bytes),
                   pushback_bytes=int(pb_bytes),
                   cache_hits=sum(1 for o in outs if o.cache),
                   n_demoted=n_dem, retries=retries,
                   faults_injected=injected,
                   outcomes=outs)
    return SplitExecution(merged, outs, n_pd, n_pb, pd_bytes, pb_bytes,
                          n_demoted=n_dem, retries=retries,
                          faults_injected=injected)


def reconcile_net_bytes(sim, reqs, split: SplitExecution) -> Dict:
    """Line real shipped bytes up against the simulator's ``net_bytes``.

    The pushback component must match exactly (both sides count the stored
    accessed-column bytes); the pushdown component differs by exactly the
    cost model's ``s_out`` cardinality-estimation error, surfaced as
    ``s_out_estimate_ratio`` (sim / real — 1.0 means the estimate was
    spot-on) plus a per-table breakdown the ``CardinalityCorrector``
    learns from."""
    decisions = sim.decisions()
    sim_pd = sum(r.cost.s_out for r in reqs
                 if decisions.get(r.req_id, PUSHDOWN) == PUSHDOWN)
    sim_pb = sum(r.cost.s_in for r in reqs
                 if decisions.get(r.req_id, PUSHDOWN) == PUSHBACK)
    by_table: Dict[str, Dict[str, float]] = {}
    real_pd_by_id = {o.req_id: o.shipped_bytes for o in split.outcomes
                     if o.path == PUSHDOWN}
    for r in reqs:
        if r.req_id not in real_pd_by_id:
            continue
        row = by_table.setdefault(r.table, {"sim_pushdown_bytes": 0,
                                            "real_pushdown_bytes": 0})
        row["sim_pushdown_bytes"] += r.cost.s_out
        row["real_pushdown_bytes"] += real_pd_by_id[r.req_id]
    for row in by_table.values():
        row["s_out_estimate_ratio"] = (
            row["sim_pushdown_bytes"] / row["real_pushdown_bytes"]
            if row["real_pushdown_bytes"] else None)
    return {
        "sim_net_bytes": sim_pd + sim_pb,
        "real_net_bytes": split.real_net_bytes,
        "sim_pushdown_bytes": sim_pd,
        "real_pushdown_bytes": split.pushdown_bytes,
        "sim_pushback_bytes": sim_pb,
        "real_pushback_bytes": split.pushback_bytes,
        "s_out_estimate_ratio": (sim_pd / split.pushdown_bytes
                                 if split.pushdown_bytes else None),
        "by_table": by_table,
    }


def feed_corrector(corrector: CardinalityCorrector, qid: str, reqs,
                   outcomes: Sequence[RequestOutcome]) -> None:
    """Feed one executed decision split back into the corrector: per
    (table, frontier signature), the summed *uncorrected* ``s_out``
    estimate of the pushdown requests against the bytes they actually
    shipped. Pushback requests are skipped — their byte estimate (stored
    ``s_in``) is exact by construction, there is nothing to learn."""
    real_by_id = {o.req_id: o.shipped_bytes for o in outcomes
                  if o.path == PUSHDOWN}
    groups: Dict[Tuple[str, str], List] = {}
    for r in reqs:
        if r.req_id in real_by_id:
            groups.setdefault((r.table, plan_signature(r.plan)),
                              []).append(r)
    for (table, sig), rs in groups.items():
        est = sum(r.s_out_raw or r.cost.s_out for r in rs)
        real = sum(real_by_id[r.req_id] for r in rs)
        corrector.observe(qid, table, sig, est, real)


# ------------------------------------------------- concurrent stream driver
@dataclasses.dataclass
class StreamQuery:
    query: object                 # queries.Query
    arrival: float = 0.0          # seconds after stream start


@dataclasses.dataclass
class StreamRun:
    mode: str
    wall_clock: float                      # execution makespan, seconds
    t_decide: float                        # plan + arbitration (fluid sim)
    #   seconds — kept OUT of wall_clock: the Python fluid simulator
    #   stands in for the storage node's microsecond-scale arbitration,
    #   so its interpreter cost is an artifact, not a runtime cost
    per_query: Dict[str, Dict]             # qid -> timings + split counts
    results: Dict[str, ColumnTable]        # qid -> final query result
    sim: object                            # the shared SimResult
    n_pushdown: int
    n_pushback: int
    real_net_bytes: int
    # ---- recovery accounting (zero on fault-free, hedge-free runs)
    n_demoted: int = 0
    retries: int = 0
    hedged: int = 0                        # hedge races won by the duplicate


def _ship(cplan: CompiledPushPlan, parts_data: List[ColumnTable]
          ) -> List[ColumnTable]:
    """The pushback transfer: materialize (copy) the raw accessed-column
    projection of each partition — the driver actually moves the ``s_in``
    bytes instead of handing the replay an in-place view."""
    shipped = []
    for d in parts_data:
        proj = cplan.raw_projection(d)
        shipped.append(ColumnTable(
            {c: np.array(v, copy=True) for c, v in proj.cols.items()},
            stats=proj._stats))
    return shipped


def _ship_traced(cplan: CompiledPushPlan, parts_data: List[ColumnTable],
                 parent: Optional[obs_trace.Span] = None,
                 node: Optional[int] = None) -> List[ColumnTable]:
    """``_ship`` under a ``pushback_ship`` span (its ``ship_bytes`` is the
    stored ``s_in`` the transfer moves — the same bytes ``pushback_bytes``
    charges, counted once by the matching ``compute_replay`` span)."""
    tr = obs_trace.get_tracer()
    with tr.span("pushback_ship", parent=parent,
                 n_parts=len(parts_data), node=node) as sp:
        out = _ship(cplan, parts_data)
        if tr.enabled:
            sp.set(ship_bytes=int(sum(pushback_bytes(cplan, d)
                                      for d in parts_data)))
    return out


def run_stream(stream: Sequence[StreamQuery], catalog, cfg,
               time_scale: float = 1.0) -> StreamRun:
    """Drive an arrival-timed multi-query stream through real split
    execution on per-node worker pools sized by the slot pools.

    Per storage node: ``res.pd_slots`` pushdown-execution workers and
    ``res.pb_slots`` transfer workers (a pushback slot is the transfer
    stream, as in the simulator); a compute pool replays pushed-back
    batches and runs each query's residual ``compute``. Dispatch order
    within a query follows the Arbitrator's live decision callback, so the
    arbitration both *chooses the path* and *orders the work*. A query id
    appearing several times in one stream is keyed ``qid``, ``qid#1``, ...
    in ``per_query``/``results``.
    """
    from repro.core import engine as _engine  # deferred: engine imports us
    from repro.core.simulator import SimRequest, simulate

    tr = obs_trace.get_tracer()
    metrics = get_metrics()
    stream_cm = tr.span("run_stream", mode=cfg.mode, n_queries=len(stream))
    stream_span = stream_cm.__enter__()
    try:
        return _run_stream_body(stream, catalog, cfg, time_scale, tr,
                                metrics, stream_span, _engine, SimRequest,
                                simulate)
    finally:
        stream_cm.__exit__(None, None, None)


def _run_stream_body(stream, catalog, cfg, time_scale, tr, metrics,
                     stream_span, _engine, SimRequest, simulate) -> StreamRun:
    t_plan0 = time.perf_counter()
    ordered = sorted(stream, key=lambda s: s.arrival)
    # each stream entry gets a unique key so the same query id may appear
    # several times in one stream (a repeated-query workload): duplicates
    # become "Q1#1", "Q1#2", ... in per_query/results
    seen: Dict[str, int] = {}
    keys: List[str] = []
    for sq in ordered:
        n = seen.get(sq.query.qid, 0)
        seen[sq.query.qid] = n + 1
        keys.append(sq.query.qid if n == 0 else f"{sq.query.qid}#{n}")
    all_reqs: List = []
    reqs_by_key: Dict[str, List] = {}
    cache = getattr(cfg, "result_cache", None)
    for key, sq in zip(keys, ordered):
        reqs = _engine.plan_requests(sq.query, catalog,
                                     start_id=len(all_reqs),
                                     corrector=cfg.corrector,
                                     cache=cache)
        for r in reqs:
            r.query_id = key   # one sim/stream identity per stream entry
        reqs_by_key[key] = reqs
        all_reqs.extend(reqs)
    arrival_of = dict(zip(keys, (sq.arrival for sq in ordered)))
    sim_reqs = [SimRequest(r.req_id, r.part.node_id, r.query_id, r.cost,
                           arrival=arrival_of[r.query_id])
                for r in all_reqs]
    decision_pos: Dict[int, int] = {}
    sim = simulate(sim_reqs, cfg.res, cfg.mode,
                   on_decision=lambda rid, _path: decision_pos.setdefault(
                       rid, len(decision_pos)),
                   measured=_engine._measured_of(cfg),
                   breaker=getattr(cfg, "breaker", None))
    decisions = sim.decisions()
    t_decide = time.perf_counter() - t_plan0

    nodes = sorted({r.part.node_id for r in all_reqs})
    # worker pools sized by the slot pools, capped at each node's fair
    # share of the machine's real cores — and a machine-wide semaphore
    # capping *running* tasks at the physical core count: the pools carry
    # the paper's queueing semantics (which path waits on which slot
    # class), the semaphore carries the physics (a slot beyond the real
    # CPUs adds GIL churn and cache thrash, not service rate; without it
    # the adaptive mix runs both path families at once and oversubscribes
    # where the forced baselines don't). The fluid simulator models the
    # full 16-vCPU node; the real driver measures what this container can
    # actually run.
    ncpu = os.cpu_count() or 1
    per_node = max(1, ncpu // max(1, len(nodes)))
    cores = threading.BoundedSemaphore(ncpu)
    exec_pools = {n: ThreadPoolExecutor(
        max(1, min(cfg.res.pd_slots, per_node))) for n in nodes}
    ship_pools = {n: ThreadPoolExecutor(
        max(1, min(cfg.res.pb_slots, per_node))) for n in nodes}
    compute_pool = ThreadPoolExecutor(
        max(1, min(2 * cfg.num_compute_nodes, ncpu)))
    finish_pool = ThreadPoolExecutor(max(1, min(len(ordered),
                                                max(2, ncpu))))
    threshold = cfg.filter_gather_threshold

    # fault-tolerance wiring (core.faults; getattr: plain configs without
    # the fields — and older pickled ones — stay fault-free)
    faults = getattr(cfg, "faults", None)
    if faults is None:
        faults = _faults.env_plan()
    # storage tier (distributed.workers): "process" dispatches every
    # storage-side group to real worker processes over the wire; real
    # channel faults must flow through retry -> demote, so the recovery
    # loop is always armed on this tier
    tier = _engine.resolve_tier(cfg, catalog)
    retry = getattr(cfg, "retry", None)
    if (faults is not None or tier is not None) and retry is None:
        retry = _faults.RetryPolicy()
    recovered = faults is not None or tier is not None
    hedge = getattr(cfg, "hedge", None)
    breaker = getattr(cfg, "breaker", None)
    exec_samples: List[float] = []     # storage-execute durations (hedging
    samples_lock = threading.Lock()    # calibrates its delay from these)

    def on_core(fn, *args, **kw):
        with cores:
            return fn(*args, **kw)

    # on the process tier the submitting thread mostly *waits* on the wire
    # while the worker process burns its own cores — gating dispatch on
    # the parent's core semaphore would serialize I/O, not CPU
    gate = on_core if tier is None else (lambda fn, *a, **kw: fn(*a, **kw))

    def exec_group(cplan, sub, path, shipped=None, qspan=None, node=None,
                   salt="", abort=None):
        """One storage-execute (or replay) group, through the recovery
        loop when a fault plan or the process tier is active; always
        returns the uniform ``(out, span, GroupRecovery-or-None)`` triple
        and records its duration for hedge-delay calibration — unless its
        ``abort`` token was set (a lost hedge race must not pollute the
        calibration stream; ``stream.exec_samples`` counts exactly the
        recorded ones)."""
        t_ex = time.perf_counter()
        if not recovered:
            out, sp = _exec_group_traced(cplan, sub, path, cfg.executor,
                                         threshold, shipped=shipped,
                                         parent=qspan, node=node,
                                         cache=cache)
            rec = None
        else:
            out, sp, rec = _exec_group_recovered(
                cplan, sub, path, cfg.executor, threshold, faults, retry,
                breaker=breaker, shipped=shipped, parent=qspan, node=node,
                cache=cache, salt=salt, tier=tier, abort=abort)
        if abort is None or not abort.is_set():
            with samples_lock:
                exec_samples.append(time.perf_counter() - t_ex)
            metrics.counter("stream.exec_samples").inc()
        return out, sp, rec

    def sample_wave(qspan) -> None:
        """Per-wave load signals: on the in-process tier, slot-pool queue
        depths + free cores; on the process tier, each *worker's* live
        queue-depth / in-flight / CPU-occupancy snapshot polled over the
        wire (``WorkerPool.publish_load``) — written to the very metrics
        gauges the Arbitrator's ``MeasuredLoad`` consumes every dispatch
        wave and, when tracing, stamped on the query as a ``wave_sample``
        instant."""
        cores_free = getattr(cores, "_value", None)
        if cores_free is not None:
            metrics.gauge("stream.cores_free").set(cores_free)
        if tier is not None:
            loads = tier.publish_load()
            if tr.enabled:
                tr.event("wave_sample", parent=qspan, worker_loads=loads,
                         cores_free=cores_free)
            return
        exec_q = {n: exec_pools[n]._work_queue.qsize() for n in nodes}
        ship_q = {n: ship_pools[n]._work_queue.qsize() for n in nodes}
        for n in nodes:
            metrics.gauge(f"stream.node{n}.exec_queue").set(exec_q[n])
            metrics.gauge(f"stream.node{n}.ship_queue").set(ship_q[n])
        if tr.enabled:
            tr.event("wave_sample", parent=qspan,
                     exec_queue=exec_q, ship_queue=ship_q,
                     cores_free=cores_free)

    def submit_query(key: str, qspan) -> List[Tuple[object, Future]]:
        """Fan the query's requests out as (req-group, future) chunks."""
        sample_wave(qspan)
        chunks: Dict[Tuple[str, int, int, str], List] = {}
        for r in reqs_by_key[key]:
            path = decisions.get(r.req_id, PUSHDOWN)
            chunks.setdefault(
                (r.table, id(r.plan), r.part.node_id, path), []).append(r)
        futs: List[Tuple[object, Future]] = []
        for (table, _pid, node, path), sub in sorted(
                chunks.items(),
                key=lambda kv: min(decision_pos.get(r.req_id, 0)
                                   for r in kv[1])):
            cplan = compile_push_plan(sub[0].plan)
            abort = threading.Event() if hedge is not None else None
            if path == PUSHDOWN:
                fut = exec_pools[node].submit(
                    gate, exec_group, cplan, sub, path,
                    qspan=qspan, node=node, abort=abort)
            elif tier is not None:
                # process tier: the fetch is a real wire transfer made
                # inside the recovery loop (a dead worker mid-fetch must
                # flow retry -> local replay, not error) — one future on
                # the node's transfer pool, replay inline after decode
                fut = ship_pools[node].submit(
                    gate, exec_group, cplan, sub, path,
                    qspan=qspan, node=node, abort=abort)
            else:
                ship_fut = ship_pools[node].submit(
                    on_core, _ship_traced, cplan,
                    [r.part.data for r in sub], parent=qspan, node=node)
                # wait for the transfer OUTSIDE the core gate, replay inside
                fut = compute_pool.submit(
                    lambda cp=cplan, s=sub, sf=ship_fut, qs=qspan, nd=node,
                    ab=abort:
                    on_core(exec_group, cp, s, PUSHBACK,
                            shipped=sf.result(), qspan=qs, node=nd,
                            abort=ab))
            futs.append(((sub, path, cplan, node, abort), fut))
        return futs

    t0 = time.perf_counter()

    def resolve(meta, fut, qspan):
        """Await one group future, hedging pushdown stragglers: when the
        original outlives the calibrated percentile delay, a duplicate
        launches on the same node's exec pool (salted so its fault draws
        differ — a retried RPC, not a replayed one); first completion
        wins, the loser is cancelled if still queued and its **abort
        token is set** otherwise: a thread cannot be killed mid-attempt,
        but the token makes the running loser bail at its next attempt
        boundary (``HedgeAborted``) and suppresses its calibration
        sample — a lost race never double-counts shipped bytes,
        fault-ledger entries, or ``exec_samples`` updates (the winner is
        the only future whose results reach the accounting). Returns
        ``(out, span, rec, hedge_won)``."""
        sub, path, _cplan, node, abort = meta
        delay = None
        if hedge is not None and path == PUSHDOWN:
            with samples_lock:
                delay = hedge.delay_s(exec_samples)
        if delay is None:
            return (*fut.result(), False)
        try:
            return (*fut.result(timeout=delay), False)
        except FutTimeout:
            pass
        metrics.counter("hedge.launched").inc()
        if tr.enabled:
            tr.event("hedge", parent=qspan, node=node,
                     table=sub[0].table, delay_s=delay)
        dup_abort = threading.Event()
        dup = exec_pools[node].submit(gate, exec_group, _cplan, sub,
                                      path, qspan=qspan, node=node,
                                      salt="hedge", abort=dup_abort)
        done, _ = fut_wait({fut, dup}, return_when=FIRST_COMPLETED)
        winner = fut if fut in done else dup       # original preferred
        loser, loser_abort = (dup, dup_abort) if winner is fut \
            else (fut, abort)
        loser.cancel()
        if loser_abort is not None:
            loser_abort.set()
        won = winner is dup
        metrics.counter("hedge.won" if won else "hedge.lost").inc()
        return (*winner.result(), won)

    def finish_query(key: str, sq: StreamQuery, futs, qspan) -> Dict:
        try:
            return _finish_query(key, sq, futs, qspan)
        except BaseException as e:
            # a failed worker must neither leak the open query span nor
            # swallow its error: close the span with the failure attached
            # and re-raise — the driver surfaces it after draining peers
            if tr.enabled:
                tr.end(qspan, error=repr(e))
            raise

    def _finish_query(key: str, sq: StreamQuery, futs, qspan) -> Dict:
        per_req: Dict[int, ColumnTable] = {}
        outcomes: List[RequestOutcome] = []
        n_pd = n_pb = n_hit = n_dem = n_retry = n_hedge = 0
        pd_b = pb_b = 0
        for meta, fut in futs:
            (sub, path, cplan, node, _abort) = meta
            out, gsp, rec, hedged = resolve(meta, fut, qspan)
            eff_path = PUSHBACK if (rec is not None and rec.demoted) \
                else path
            demoted = eff_path != path
            if rec is not None:
                n_retry += rec.retries
            if hedged:
                n_hedge += 1
            g_bytes = 0
            for r, (res, aux) in zip(sub, out):
                per_req[r.req_id] = res
                if eff_path == PUSHDOWN:
                    n_pd += 1
                    b = result_bytes(res, aux)
                    pd_b += b
                else:
                    n_pb += 1
                    b = pushback_bytes(cplan, r.part.data)
                    pb_b += b
                    if demoted:
                        n_dem += 1
                g_bytes += b
                kind = aux.get("cache")
                if kind:
                    n_hit += 1
                outcomes.append(RequestOutcome(
                    r.req_id, r.table, eff_path, len(res), b,
                    replayed=(eff_path == PUSHBACK), cache=kind,
                    attempts=rec.attempts if rec is not None else 1,
                    demoted=demoted, hedged=hedged))
            tr.amend(gsp, shipped_bytes=int(g_bytes))
        if cfg.corrector is not None:
            # per-stream-entry feedback: repeated streams converge the
            # estimates (the key strips the '#n' repeat suffix — the
            # correction belongs to the query, not the stream slot)
            feed_corrector(cfg.corrector, sq.query.qid, reqs_by_key[key],
                           outcomes)
        by_table: Dict[str, List[ColumnTable]] = {}
        for r in reqs_by_key[key]:
            by_table.setdefault(r.table, []).append(per_req[r.req_id])

        def merge_and_compute():
            with tr.span("merge", parent=qspan, tables=sorted(by_table)):
                merged = {t: ColumnTable.concat(p)
                          for t, p in by_table.items()}
            backend = getattr(cfg, "residual", RESIDUAL_INTERPRETER)
            with tr.span("residual_compute", parent=qspan) as rsp:
                res, trun = run_residual(sq.query, merged, backend)
                if tr.enabled:
                    tr.amend(rsp, backend=("tensor" if trun is not None
                                           else "interpreter"),
                             jit_hits=(trun.jit_hits if trun else None),
                             jit_misses=(trun.jit_misses if trun else None))
                return res, residual_jit_info(trun)

        result, residual_jit = on_core(merge_and_compute)
        sim_pd = sum(r.cost.s_out for r in reqs_by_key[key]
                     if decisions.get(r.req_id, PUSHDOWN) == PUSHDOWN)
        finish_s = time.perf_counter() - t0
        metrics.counter("stream.requests.pushdown").inc(n_pd)
        metrics.counter("stream.requests.pushback").inc(n_pb)
        metrics.counter("stream.net_bytes.real").inc(pd_b + pb_b)
        if n_hit:
            metrics.counter("stream.cache_hits").inc(n_hit)
        if n_dem:
            metrics.counter("stream.requests.demoted").inc(n_dem)
        metrics.histogram("stream.query_finish_s").observe(finish_s)
        if tr.enabled:
            sim_pb = sum(r.cost.s_in for r in reqs_by_key[key]
                         if decisions.get(r.req_id, PUSHDOWN) == PUSHBACK)
            tr.end(qspan, real_net_bytes=int(pd_b + pb_b),
                   sim_net_bytes=int(sim_pd + sim_pb),
                   n_pushdown=n_pd, n_pushback=n_pb,
                   cache_hits=n_hit,
                   n_demoted=n_dem, retries=n_retry, hedged=n_hedge,
                   s_out_est_ratio=(sim_pd / pd_b if pd_b else None),
                   finish_s=finish_s)
        return {"result": result,
                "finish_s": finish_s,
                "n_pushdown": n_pd, "n_pushback": n_pb,
                "cache_hits": n_hit,
                "n_demoted": n_dem, "retries": n_retry, "hedged": n_hedge,
                "real_net_bytes": pd_b + pb_b,
                "s_out_estimate_ratio": (sim_pd / pd_b if pd_b else None),
                "sim_finish": sim.finish_by_query.get(key),
                "residual_jit": residual_jit}

    finishers: Dict[str, Future] = {}
    errors: Dict[str, BaseException] = {}
    per_query: Dict[str, Dict] = {}
    try:
        for key, sq in zip(keys, ordered):
            delay = t0 + sq.arrival * time_scale - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            # detached span: opened at dispatch in this thread, closed by
            # the finish-pool worker (explicit parent, no stack propagation)
            qspan = tr.start("query", parent=stream_span,
                             qid=key, mode=cfg.mode, arrival=sq.arrival)
            finishers[key] = finish_pool.submit(
                finish_query, key, sq, submit_query(key, qspan), qspan)
        # drain EVERY finisher before surfacing any failure: a worker
        # exception must not strand its peers' futures on half-shut pools
        for qid, f in finishers.items():
            try:
                per_query[qid] = f.result()
            except BaseException as e:  # noqa: BLE001 — drained, re-raised
                errors[qid] = e
        wall = time.perf_counter() - t0
    finally:
        # cancel whatever never started, then join the worker threads —
        # run_stream returns (or raises) with every pool fully shut down
        for p in (*exec_pools.values(), *ship_pools.values(),
                  compute_pool, finish_pool):
            p.shutdown(wait=True, cancel_futures=True)
    if errors:
        qid, err = next(iter(errors.items()))
        raise RuntimeError(
            f"stream query {qid!r} failed "
            f"({len(errors)}/{len(finishers)} queries errored)") from err
    results = {qid: d.pop("result") for qid, d in per_query.items()}
    if tr.enabled:
        stream_span.set(
            wall_clock=wall, t_decide=t_decide,
            n_pushdown=sum(d["n_pushdown"] for d in per_query.values()),
            n_pushback=sum(d["n_pushback"] for d in per_query.values()),
            n_demoted=sum(d["n_demoted"] for d in per_query.values()),
            retries=sum(d["retries"] for d in per_query.values()),
            hedged=sum(d["hedged"] for d in per_query.values()),
            real_net_bytes=sum(d["real_net_bytes"]
                               for d in per_query.values()))
    return StreamRun(
        mode=cfg.mode, wall_clock=wall, t_decide=t_decide,
        per_query=per_query, results=results, sim=sim,
        n_pushdown=sum(d["n_pushdown"] for d in per_query.values()),
        n_pushback=sum(d["n_pushback"] for d in per_query.values()),
        real_net_bytes=sum(d["real_net_bytes"] for d in per_query.values()),
        n_demoted=sum(d["n_demoted"] for d in per_query.values()),
        retries=sum(d["retries"] for d in per_query.values()),
        hedged=sum(d["hedged"] for d in per_query.values()))
