"""Multi-process storage tier (distributed.workers): wire codec, plan
marshalling, the in-process oracle contract (byte-identity across tiers
for any decision vector and any fault schedule), live load signals, and
real process-failure recovery through the PR-8 retry/demote machinery."""
import os
import socket
import time

import numpy as np
import pytest

from repro.core import engine, runtime
from repro.core.arbitrator import PUSHBACK, PUSHDOWN
from repro.core.executor import EXECUTOR_BATCHED, compile_push_plan
from repro.core.faults import FaultExhausted, RetryPolicy, WorkerFault
from repro.core.plan import execute_push_plan
from repro.distributed import workers as W
from repro.obs import metrics as om
from repro.obs import trace as T
from repro.queryproc import queries as Q
from repro.queryproc import tpch
from repro.queryproc.table import ColumnTable

CAT = tpch.build_catalog(sf=0.3, num_nodes=2, rows_per_partition=3_000)
FAST = RetryPolicy(sleep_scale=0.0)


@pytest.fixture(autouse=True)
def fresh_metrics():
    """Every test reconciles counters/gauges against its own registry."""
    prev = om.get_metrics()
    m = om.Metrics()
    om.set_metrics(m)
    yield m
    om.set_metrics(prev)


@pytest.fixture(scope="module")
def pool():
    """One shared pool over CAT for the non-destructive tests (the chaos
    tests fork their own so a killed worker never leaks across tests)."""
    p = W.WorkerPool(CAT, pd_slots=2)
    yield p
    p.close()


def assert_tables_identical(a: ColumnTable, b: ColumnTable, ctx=""):
    assert a.columns == b.columns, (ctx, a.columns, b.columns)
    for c in a.columns:
        x, y = a.cols[c], b.cols[c]
        assert x.dtype == y.dtype, (ctx, c, x.dtype, y.dtype)
        assert np.array_equal(x, y, equal_nan=True), (ctx, c)


def stream_of(qids, arrival=0.0):
    return [runtime.StreamQuery(Q.build_query(q), arrival) for q in qids]


def small_catalog():
    return tpch.build_catalog(sf=0.05, num_nodes=1, rows_per_partition=500)


# ---------------------------------------------------------------- the codec
def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        hdr = {"kind": "exec", "req": 7, "parts": [["lineitem", 0]]}
        body = bytes(range(256)) * 3
        sent = W._write_frame(a, hdr, body)
        got_hdr, got_body, total = W._read_frame(b)
        assert got_hdr == hdr
        assert bytes(got_body) == body
        assert total == sent          # wire-byte accounting is symmetric
    finally:
        a.close()
        b.close()


def test_value_codec_roundtrip_and_writability():
    """Everything a push-plan result/aux can hold survives the tagged
    codec — nested containers, mixed dtypes, empty arrays/tables — and
    decoded arrays are writable (the replay mutates them in place)."""
    rng = np.random.default_rng(0)
    tab = ColumnTable({"a": rng.integers(0, 9, 50).astype(np.int32),
                       "b": rng.normal(size=50),
                       "c": rng.integers(0, 2, 50).astype(bool)})
    val = {"tables": [tab, ColumnTable({"x": np.array([], np.float64)})],
           "aux": ({"bitmap": np.packbits(np.ones(17, np.uint8)),
                    "rows": 17, "sel": 0.25, "tag": "q1", "none": None},
                   [np.arange(6, dtype=np.int64).reshape(2, 3), True]),
           3: "int-keyed"}
    bufs = []
    spec = W._enc(val, bufs)
    # header side is pure JSON-able structure; bytes ride separately
    import json
    json.dumps(spec)
    # the channel always decodes out of the received bytearray — that is
    # what makes frombuffer views writable downstream
    out = W._dec(spec, W._Cursor(bytearray(b"".join(bufs))))
    t0, t1 = out["tables"]
    assert_tables_identical(tab, t0)
    assert t1.columns == ["x"] and len(t1.cols["x"]) == 0
    aux, lst = out["aux"]
    assert isinstance(out["aux"], tuple) and isinstance(lst, list)
    np.testing.assert_array_equal(aux["bitmap"],
                                  np.packbits(np.ones(17, np.uint8)))
    assert aux["rows"] == 17 and aux["sel"] == 0.25
    assert aux["none"] is None and out[3] == "int-keyed"
    np.testing.assert_array_equal(lst[0], np.arange(6).reshape(2, 3))
    t0.cols["a"][0] = 99                # writable: no read-only frombuffer
    assert t0.cols["a"][0] == 99


def test_plan_codec_survives_derive_lambdas():
    """Real query plans carry lambdas in their ``derive`` tuples — the
    marshal-backed pickler must round-trip them to a plan that executes
    byte-identically; module-level functions still pickle by reference."""
    q = Q.build_query("Q1")
    plan = q.plans["lineitem"]
    assert plan.derive                  # the plan actually carries lambdas
    spec = W.encode_plan(plan)
    back = W.decode_plan(spec)
    data = CAT.tables["lineitem"][0].data
    ref, _ = execute_push_plan(plan, data)
    got, _ = execute_push_plan(back, data)
    assert_tables_identical(ref, got, "Q1 derive")
    # stable bytes: the same plan encodes to the same spec (the pool's
    # blake2b plan_key relies on it to dedupe shipping)
    assert W.encode_plan(plan) == spec


# ---------------------------------------------------- the tier oracle (PR-4)
def test_all_queries_byte_identical_random_decision_vectors(pool):
    """The acceptance bar: all 15 TPC-H queries, random pushdown/pushback
    decision vectors, process tier vs in-process oracle — merged tables
    byte-identical."""
    rng = np.random.default_rng(7)
    for qid in Q.QUERY_IDS:
        q = Q.build_query(qid)
        reqs = engine.plan_requests(q, CAT)
        dec = {r.req_id: (PUSHDOWN if rng.random() < 0.5 else PUSHBACK)
               for r in reqs}
        ref = runtime.execute_split(reqs, dec)
        got = runtime.execute_split(reqs, dec, retry=FAST, tier=pool)
        assert set(ref.merged) == set(got.merged), qid
        for table in ref.merged:
            assert_tables_identical(ref.merged[table], got.merged[table],
                                    (qid, table))
        assert (ref.n_pushdown, ref.n_pushback) == \
            (got.n_pushdown, got.n_pushback), qid
        assert got.n_demoted == 0       # healthy workers: no recovery


def test_engine_modes_byte_identical_across_tiers(pool):
    """run_query through the full engine (arbitration included) returns
    the same result table on both tiers, for every mode."""
    for qid in ("Q1", "Q6", "Q12"):
        for mode in (engine.MODE_ADAPTIVE, engine.MODE_EAGER):
            base = engine.EngineConfig(mode=mode, measured_feedback=False)
            proc = engine.EngineConfig(mode=mode, measured_feedback=False,
                                       worker_pool=pool, retry=FAST)
            ref = engine.run_query(Q.build_query(qid), CAT, base)
            got = engine.run_query(Q.build_query(qid), CAT, proc)
            assert_tables_identical(ref.result, got.result, (qid, mode))


def test_wire_bytes_flow_and_counters(pool, fresh_metrics):
    """Pushdown results and pushback projections cross the wire as real
    serialized bytes, counted by the wire.* counters."""
    q = Q.build_query("Q6")
    reqs = engine.plan_requests(q, CAT)
    half = {r.req_id: (PUSHDOWN if i % 2 == 0 else PUSHBACK)
            for i, r in enumerate(reqs)}
    before = pool.wire_bytes()
    runtime.execute_split(reqs, half, retry=FAST, tier=pool)
    after = pool.wire_bytes()
    assert after["sent"] > before["sent"]
    assert after["recv"] > before["recv"]
    c = fresh_metrics.snapshot()["counters"]
    assert c.get("wire.pushdown_result_bytes", 0) > 0
    assert c.get("wire.pushback_ship_bytes", 0) > 0


def test_storage_tier_config_resolution():
    assert engine.resolve_tier(engine.EngineConfig(), CAT) is None
    assert engine.resolve_tier(
        engine.EngineConfig(storage_tier=None), CAT) is None
    sentinel = object()
    assert engine.resolve_tier(
        engine.EngineConfig(worker_pool=sentinel), CAT) is sentinel
    with pytest.raises(ValueError):
        engine.resolve_tier(engine.EngineConfig(storage_tier="bogus"), CAT)


def test_pool_for_registry_reuses_and_closes():
    cat = small_catalog()
    p1 = W.pool_for(cat, pd_slots=1)
    try:
        assert W.pool_for(cat) is p1      # one pool per catalog
    finally:
        W.close_all_pools()
    assert p1.closed
    p2 = W.pool_for(cat, pd_slots=1)      # a closed pool is replaced
    try:
        assert p2 is not p1 and not p2.closed
    finally:
        W.close_all_pools()


# ----------------------------------------------------------- load signals
def test_load_signals_published_and_burn_pressure(pool, fresh_metrics):
    """Every worker publishes queue-depth/in-flight/CPU; ``burn`` raises
    real storage-side pressure that shows up in the very gauges
    MeasuredLoad reads."""
    loads = pool.publish_load()
    assert set(loads) == {0, 1}
    for node, snap in loads.items():
        assert {"exec_q", "ship_q", "inflight", "done"} <= set(snap)
    g = fresh_metrics.snapshot()["gauges"]
    for node in (0, 1):
        assert f"stream.node{node}.exec_queue" in g
        assert f"stream.node{node}.ship_queue" in g
        assert f"storage.node{node}.inflight" in g
    done0 = loads[0]["done"]
    pool.burn(0, 0.05, tasks=6)           # 6 x 50ms on 2 slots
    busy = pool.publish_load()[0]
    # pressure is visible while the burn is in flight: queued + running
    assert busy["exec_q"] + busy["inflight"] > 0
    g = fresh_metrics.snapshot()["gauges"]
    assert g["stream.node0.exec_queue"] == busy["exec_q"]
    deadline = time.monotonic() + 10.0
    max_cpu = busy.get("cpu") or 0.0
    while time.monotonic() < deadline:
        snap = pool.publish_load()[0]
        max_cpu = max(max_cpu, snap.get("cpu") or 0.0)
        if snap["done"] >= done0 + 6:
            break
        time.sleep(0.02)
    assert snap["done"] >= done0 + 6
    # the burn was real CPU: occupancy peaked strictly positive while the
    # worker was grinding (each poll samples the window since the last)
    assert max_cpu > 0


# --------------------------------------------- real faults -> PR-8 recovery
def test_dead_channel_raises_workerfault_and_records():
    p = W.WorkerPool(CAT, pd_slots=1)
    try:
        p.kill(1)
        reqs = engine.plan_requests(Q.build_query("Q6"), CAT)
        sub = [r for r in reqs if r.part.node_id == 1]
        cplan = compile_push_plan(sub[0].plan)
        deadline = time.monotonic() + 5.0
        while p.alive(1) and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(WorkerFault) as ei:
            p.execute_group(cplan, sub, EXECUTOR_BATCHED, None)
        assert ei.value.kind == "crash" and ei.value.node == 1
        assert p.fault_counts() == {"crash": 1}
        assert p.alive(0)                 # the blast radius is one node
    finally:
        p.close()


def test_overdue_request_raises_workerfault_timeout():
    cat = small_catalog()
    p = W.WorkerPool(cat, pd_slots=1, request_timeout_s=0.05)
    try:
        p.burn(0, 0.6, tasks=2)           # occupy the only slot + queue
        reqs = engine.plan_requests(Q.build_query("Q6"), cat)
        cplan = compile_push_plan(reqs[0].plan)
        with pytest.raises(WorkerFault) as ei:
            p.execute_group(cplan, reqs[:1], EXECUTOR_BATCHED, None)
        assert ei.value.kind == "timeout"
        assert p.fault_counts() == {"timeout": 1}
        assert p.alive(0)                 # overdue, not dead
    finally:
        p.close()


def test_stream_worker_kill_mid_wave_recovers_and_reconciles():
    """Satellite 4: SIGKILL a storage worker mid-wave (the worker's own
    pinned die_after schedule — deterministic by work-item count) and the
    stream must recover via retry -> demote-to-pushback with results
    byte-identical to the clean in-process run, and the pool's real-fault
    ledger reconciling exactly with the faults.* counters."""
    qids = ["Q1", "Q6", "Q12"]
    clean = runtime.run_stream(stream_of(qids), CAT,
                               engine.EngineConfig(measured_feedback=False),
                               time_scale=0)
    om.set_metrics(om.Metrics())          # isolate the chaotic run's ledger
    p = W.WorkerPool(CAT, pd_slots=2)
    try:
        p.die_after(0, 2)                 # node 0 dies at its 3rd work item
        cfg = engine.EngineConfig(worker_pool=p, retry=FAST,
                                  measured_feedback=False)
        run = runtime.run_stream(stream_of(qids), CAT, cfg, time_scale=0)
        for qid in qids:
            assert_tables_identical(clean.results[qid], run.results[qid],
                                    qid)
        assert not p.alive(0) and p.alive(1)
        assert run.n_demoted > 0          # recovery actually happened
        c = om.get_metrics().snapshot()["counters"]
        events = p.events
        assert len(events) > 0 and all(ev["node"] == 0 for ev in events)
        # exact reconciliation: every channel fault the pool recorded was
        # counted once by the recovery loop, by kind and by (node, path)
        assert c.get("faults.crash", 0) + c.get("faults.timeout", 0) == \
            len(events)
        per_node_path = sum(v for k, v in c.items()
                            if k.startswith("faults.node")
                            and k.endswith(".failures"))
        assert per_node_path == len(events)
        assert c.get("retry.demotions", 0) + \
            c.get("retry.local_replays", 0) > 0
        assert run.retries == c.get("retry.attempts", 0)
    finally:
        p.close()


def test_stream_worker_kill_no_demote_aggregates_error():
    """With ``demote_on_exhaust=False`` (the fail-to-error baseline) a
    killed worker surfaces as the aggregated RuntimeError whose cause is
    the FaultExhausted — not a hang, not a silent wrong answer."""
    p = W.WorkerPool(CAT, pd_slots=2)
    try:
        p.die_after(0, 0)                 # first work item kills node 0
        cfg = engine.EngineConfig(
            worker_pool=p,
            retry=RetryPolicy(sleep_scale=0.0, demote_on_exhaust=False),
            measured_feedback=False)
        with pytest.raises(RuntimeError) as ei:
            runtime.run_stream(stream_of(["Q6"]), CAT, cfg, time_scale=0)
        assert isinstance(ei.value.__cause__, FaultExhausted)
        assert ei.value.__cause__.kind == "crash"
    finally:
        p.close()


def test_split_recovery_after_kill_is_byte_identical():
    """execute_split (no stream) against a freshly killed worker: every
    node-0 group demotes, results stay byte-identical, outcomes carry the
    recovery accounting."""
    p = W.WorkerPool(CAT, pd_slots=1)
    try:
        p.kill(0)
        deadline = time.monotonic() + 5.0
        while p.alive(0) and time.monotonic() < deadline:
            time.sleep(0.01)
        q = Q.build_query("Q14")
        reqs = engine.plan_requests(q, CAT)
        dec = {r.req_id: PUSHDOWN for r in reqs}
        ref = runtime.execute_split(reqs, dec)
        got = runtime.execute_split(reqs, dec, retry=FAST, tier=p)
        for table in ref.merged:
            assert_tables_identical(ref.merged[table], got.merged[table],
                                    table)
        assert got.n_demoted == sum(1 for r in reqs
                                    if r.part.node_id == 0)
        demoted = {o.req_id for o in got.outcomes if o.demoted}
        assert demoted == {r.req_id for r in reqs if r.part.node_id == 0}
    finally:
        p.close()


# ------------------------------------------------------ staleness + tracing
def test_catalog_mutation_triggers_reship():
    """append_to_partition bumps the version stamp; the pool re-ships the
    stale partition so the worker never serves old bytes."""
    cat = small_catalog()
    p = W.WorkerPool(cat, pd_slots=1)
    try:
        q = Q.build_query("Q6")
        reqs = engine.plan_requests(q, cat)
        dec = {r.req_id: PUSHDOWN for r in reqs}
        before = runtime.execute_split(reqs, dec, retry=FAST, tier=p)
        part = cat.tables["lineitem"][0]
        extra = ColumnTable({c: np.asarray(v)[:64]
                             for c, v in part.data.cols.items()})
        cat.append_to_partition("lineitem", 0, extra)
        reqs2 = engine.plan_requests(q, cat)
        dec2 = {r.req_id: PUSHDOWN for r in reqs2}
        ref = runtime.execute_split(reqs2, dec2)
        got = runtime.execute_split(reqs2, dec2, retry=FAST, tier=p)
        assert_tables_identical(ref.merged["lineitem"],
                                got.merged["lineitem"], "post-append")
        # the result really moved: stale bytes would have reproduced
        # `before` instead
        b, g = before.merged["lineitem"], got.merged["lineitem"]
        assert any(not np.array_equal(b.cols[c], g.cols[c])
                   for c in b.columns)
    finally:
        p.close()


def _queue_and_work(tr):
    """Each adopted ``worker_queue`` span paired with the handling span of
    the same request: same dispatching span, starting where the wait
    ends."""
    spans = tr.snapshot()
    work = [s for s in spans if s.name in ("worker_execute", "worker_fetch")]
    return [(q, w) for q in spans if q.name == "worker_queue"
            for w in work if w.parent == q.parent
            and abs(w.t0 - (q.t0 + q.dur)) < 1e-9]


def test_worker_spans_stitched_into_compute_trace(pool):
    """Span-id handoff: worker-side spans come back in the response and
    are adopted under the dispatching compute-side span, echoing it as
    ``remote_parent`` and carrying the worker's pid. Each request's
    ``worker_queue`` ends where its ``worker_execute``/``worker_fetch``
    starts, and both lie inside the dispatching span."""
    q = Q.build_query("Q6")
    reqs = engine.plan_requests(q, CAT)
    dec = {r.req_id: (PUSHDOWN if i % 2 == 0 else PUSHBACK)
           for i, r in enumerate(reqs)}
    with T.tracing() as tr:
        runtime.execute_split(reqs, dec, retry=FAST, tier=pool)
    execs = tr.find("worker_execute")
    fetches = tr.find("worker_fetch")
    queues = tr.find("worker_queue")
    assert execs and fetches
    assert len(queues) == len(execs) + len(fetches)
    sids = {s.sid: s for s in tr.snapshot()}
    for sp in execs + fetches + queues:
        assert sp.cat == "worker"
        assert sp.attrs["pid"] != os.getpid()     # really remote
        assert sp.dur is not None and sp.dur >= 0
        assert sp.parent is not None
        assert sp.attrs["remote_parent"] == sp.parent
        parent = sids[sp.parent]
        assert parent.name in ("storage_execute", "compute_replay")
        assert parent.t0 <= sp.t0
        assert sp.t0 + sp.dur <= parent.t0 + parent.dur
    pairs = _queue_and_work(tr)
    assert len(pairs) == len(queues)
    for wq, work in pairs:
        assert work.attrs["pid"] == wq.attrs["pid"]
    nodes = {sp.attrs["node"] for sp in execs}
    assert nodes <= {0, 1}


def test_worker_spans_sit_at_the_workers_own_times(pool):
    """A request that waits behind busy slots shows the wait as
    ``worker_queue``; its ``worker_execute`` starts when a slot picked it
    up, not when the parent sent it."""
    q = Q.build_query("Q6")
    reqs = [r for r in engine.plan_requests(q, CAT)
            if r.part.node_id == 0]
    dec = {r.req_id: PUSHDOWN for r in reqs}
    burn_s = 0.4
    with T.tracing() as tr:
        t_sent = time.perf_counter() - tr.t0
        pool.burn(0, burn_s, tasks=2)      # both slots of node 0
        runtime.execute_split(reqs, dec, retry=FAST, tier=pool)
    pairs = _queue_and_work(tr)
    assert pairs
    first = min(pairs, key=lambda p: p[0].t0)
    wq, work = first
    assert work.name == "worker_execute"
    assert wq.t0 >= t_sent
    assert wq.dur >= 0.5 * burn_s
    assert work.t0 >= t_sent + 0.5 * burn_s
