"""Observability subsystem: span trees, decision channels, metrics, and
exporters — plus the two hard guarantees the tentpole promises:

1. **Byte identity**: tracing ON and OFF produce byte-identical query
   results across all 15 TPC-H queries and all 4 engine modes (the hooks
   observe, they never steer).
2. **Exact reconciliation**: the bytes a trace's execution spans claim
   were shipped equal ``QueryRun.real_net_bytes`` / the stream driver's
   per-query accounting *exactly* — same arithmetic, not a re-estimate.
"""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import engine, runtime
from repro.core.cost import StorageResources
from repro.obs import export as obs_export
from repro.obs.metrics import Metrics
from repro.obs.trace import (NULL_SPAN, NULL_TRACER, DecisionChannel, Tracer,
                             get_tracer, set_tracer, tracing)
from repro.queryproc import queries as Q
from repro.queryproc import tpch
from repro.queryproc.table import ColumnTable

CAT = tpch.build_catalog(sf=1.0, num_nodes=2, rows_per_partition=4_000)


def assert_tables_identical(a: ColumnTable, b: ColumnTable, ctx=""):
    assert a.columns == b.columns, (ctx, a.columns, b.columns)
    for c in a.columns:
        x, y = a.cols[c], b.cols[c]
        assert x.dtype == y.dtype, (ctx, c, x.dtype, y.dtype)
        assert np.array_equal(x, y, equal_nan=True), (ctx, c)


# ------------------------------------------------------------- tracer core
def test_default_tracer_is_disabled_noop():
    tr = get_tracer()
    assert tr is NULL_TRACER and not tr.enabled
    with tr.span("anything", foo=1) as sp:
        assert not sp                      # falsy null span
        sp.set(bar=2)                      # swallowed
    assert tr.snapshot() == [] and tr.tree() == []
    assert tr.start("x") is NULL_SPAN
    tr.end(NULL_SPAN, y=3)                 # no-op, no error


def test_span_nesting_and_parenting():
    with tracing() as tr:
        with tr.span("a") as a:
            with tr.span("b"):
                tr.event("e")
            det = tr.start("c", parent=a)
        tr.end(det, done=True)
    (ra,) = tr.tree()
    assert ra["name"] == "a"
    assert [c["name"] for c in ra["children"]] == ["b", "c"]
    assert ra["children"][0]["children"][0]["name"] == "e"
    assert ra["children"][0]["children"][0]["dur"] == 0.0
    assert ra["children"][1]["attrs"] == {"done": True}
    assert all(s.dur is not None for s in tr.snapshot())


def test_tracer_max_spans_drops_not_grows():
    tr = Tracer(max_spans=3)
    with tracing(tr):
        for _ in range(10):
            tr.event("e")
    assert len(tr.snapshot()) == 3 and tr.dropped == 7


def test_cross_thread_detached_span():
    with tracing() as tr:
        root = tr.start("root")

        def worker():
            with tr.span("child", parent=root):
                pass
            tr.end(root)

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    (rt,) = tr.tree()
    assert rt["name"] == "root" and rt["dur"] is not None
    assert [c["name"] for c in rt["children"]] == ["child"]


# -------------------------------------------------------- decision channel
def test_decision_channel_cap_and_counts():
    ch = DecisionChannel(cap=4)
    for i in range(10):
        ch.record(branch="gather" if i % 2 else "concat", i=i)
    assert len(ch) == 4 and ch.dropped == 6
    assert sum(ch.counts("branch").values()) == 4
    ch.clear()
    assert len(ch) == 0 and ch.dropped == 0


def test_decision_channel_thread_safety():
    ch = DecisionChannel(cap=50_000)
    n_threads, per = 8, 2_000

    def writer(k):
        for i in range(per):
            ch.record(k=k, i=i)

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ch) == n_threads * per and ch.dropped == 0
    assert ch.counts("k") == {k: per for k in range(n_threads)}


def _profiled(tmp_path, body):
    """Run ``body`` under the JAX profiler; the names of the host events
    the trace holds under the program-span prefix."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    names = []
    for path in tmp_path.glob("**/*.xplane.pb"):
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                names += [e.name for e in line.events
                          if e.name.startswith(obs.PROFILER_PREFIX)]
    return names


def test_same_thread_spans_appear_in_the_profiler_trace(tmp_path):
    """Every same-thread span of an enabled tracer sits in the profiler
    trace under the prefix, one event per span, from every thread;
    detached and instant spans stay host-clock only."""
    def body():
        with tracing() as tr:
            def work(k):
                with tr.span(f"outer{k}"):
                    with tr.span("inner"):
                        pass
            threads = [threading.Thread(target=work, args=(k,))
                       for k in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            det = tr.start("detached")
            tr.event("instant")
            tr.end(det)

    names = _profiled(tmp_path, body)
    p = obs.PROFILER_PREFIX
    assert sorted(names) == sorted([p + "inner", p + "inner",
                                    p + "outer0", p + "outer1"])


def test_disabled_tracer_emits_nothing_to_the_profiler(tmp_path):
    def body():
        tr = get_tracer()
        assert tr is NULL_TRACER
        for _ in range(3):
            with tr.span("residual_prep"):
                pass

    assert _profiled(tmp_path, body) == []


# --------------------------------------------------------------- metrics
def test_metrics_registry_and_epoch():
    m = Metrics()
    m.counter("a").inc()
    m.counter("a").inc(4)
    m.gauge("g").set(2.5)
    for v in (1, 2, 1000):
        m.histogram("h").observe(v)
    snap = m.snapshot()
    assert snap["counters"]["a"] == 5.0 and snap["gauges"]["g"] == 2.5
    assert snap["histograms"]["h"]["count"] == 3
    e1 = m.epoch()
    assert e1["counters"]["a"] == 5.0
    m.counter("a").inc(2)
    e2 = m.epoch()
    assert e2["counters"]["a"] == 2.0      # delta since previous epoch
    assert e2["epoch"] == e1["epoch"] + 1


def test_metrics_thread_safety():
    m = Metrics()
    n_threads, per = 8, 5_000

    def worker():
        for i in range(per):
            m.counter("c").inc()
            m.histogram("h").observe(i)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = m.snapshot()
    assert snap["counters"]["c"] == n_threads * per
    assert snap["histograms"]["h"]["count"] == n_threads * per


# ------------------------------------------------- span-tree goldens
def _names(node):
    return (node["name"], [_names(c) for c in node["children"]])


def test_span_tree_golden_q1():
    cfg = engine.EngineConfig(mode="adaptive")
    with tracing() as tr:
        engine.run_query(Q.build_query("Q1"), CAT, cfg)
    (qt,) = tr.tree()
    assert qt["name"] == "query" and qt["attrs"]["qid"] == "Q1"
    children = [c["name"] for c in qt["children"]]
    assert children == ["plan_requests", "arbitrate", "execute_split",
                        "residual_compute"]
    es = qt["children"][2]
    inner = [c["name"] for c in es["children"]]
    assert inner[-1] == "merge" and "storage_execute" in inner
    assert es["attrs"]["pushdown_bytes"] + es["attrs"]["pushback_bytes"] \
        == qt["attrs"]["real_net_bytes"]


def test_span_tree_golden_q19_costed():
    from repro.compiler import compile as C
    with tracing() as tr:
        cq = C.compile_query_costed("q19", CAT)
        engine.run_query(cq.query, CAT, engine.EngineConfig(mode="adaptive"))
    roots = [t["name"] for t in tr.tree()]
    assert roots == ["compile", "query"]
    comp = tr.tree()[0]
    cuts = [c for c in comp["children"] if c["name"] == "cut_scoring"]
    assert {c["attrs"]["table"] for c in cuts} == {"lineitem", "part"}
    for c in cuts:
        assert len(c["attrs"]["scores"]) == len(c["attrs"]["signatures"]) \
            == c["attrs"]["maximal"] + 1
        assert 0 <= c["attrs"]["chosen"] <= c["attrs"]["maximal"]


def test_span_tree_golden_q18_clustered_having():
    """The clustered-catalog Q18 trace shows the HAVING frontier: the
    chooser's ``cut_scoring`` event picks the ``scan+agg+having``
    candidate and the executed plan's signature carries it."""
    from repro.compiler import compile as C
    ccat = tpch.build_catalog(sf=1.0, num_nodes=2, rows_per_partition=4_000,
                              cluster={"lineitem": "l_orderkey"})
    with tracing() as tr:
        cq = C.compile_query_costed("q18", ccat)
        engine.run_query(cq.query, ccat, engine.EngineConfig(mode="adaptive"))
    (cut,) = [c for c in tr.tree()[0]["children"]
              if c["name"] == "cut_scoring"
              and c["attrs"]["table"] == "lineitem"]
    assert cut["attrs"]["signatures"][cut["attrs"]["chosen"]] \
        == "scan+agg+having"
    sigs = {s.attrs.get("signature") for s in tr.find("storage_execute")}
    assert "scan+agg+having" in sigs


def test_arbitrate_decision_channel_records_load():
    with tracing() as tr:
        engine.run_query(Q.build_query("Q6"), CAT,
                         engine.EngineConfig(mode="adaptive"))
    decs = tr.decisions.snapshot()
    assert len(decs) == len(engine.plan_requests(Q.build_query("Q6"), CAT))
    for d in decs:
        assert d["kind"] == "arbitrate"
        assert d["path"] in ("pushdown", "pushback")
        assert d["free_pd"] >= 0 and d["free_pb"] >= 0 \
            and d["queue_depth"] >= 0


# ------------------------------------- byte identity: tracing on vs off
@pytest.mark.parametrize("qid", Q.QUERY_IDS)
def test_tracing_byte_identity_all_modes(qid):
    q = Q.build_query(qid)
    for mode in engine.MODES:
        cfg = engine.EngineConfig(mode=mode)
        base = engine.run_query(q, CAT, cfg)           # tracing off
        with tracing():
            traced = engine.run_query(q, CAT, cfg)     # tracing on
        assert_tables_identical(base.result, traced.result, (qid, mode))
        assert base.real_net_bytes == traced.real_net_bytes, (qid, mode)


# --------------------------------------------------------- exporters
def _traced_q1():
    with tracing() as tr:
        engine.run_query(Q.build_query("Q1"), CAT,
                         engine.EngineConfig(mode="adaptive"))
    return tr


def test_jsonl_round_trip_tree_equality(tmp_path):
    tr = _traced_q1()
    path = tmp_path / "trace.jsonl"
    obs_export.to_jsonl(tr, path, meta={"suite": "test"})
    meta, spans = obs_export.from_jsonl(path)
    assert meta["format"] == "repro-trace-v1"
    assert meta["n_spans"] == len(tr.snapshot()) and meta["suite"] == "test"
    # round-tripped forest == the tracer's own (after JSON coercion)
    want = json.loads(json.dumps(tr.tree(), default=obs_export._coerce))
    assert obs_export.build_tree(spans) == want


def test_chrome_trace_is_valid_and_complete(tmp_path):
    tr = _traced_q1()
    path = tmp_path / "trace.json"
    obs_export.to_chrome_trace(tr, path, meta={"mode": "adaptive"})
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"                      # process_name meta
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == len(tr.snapshot())
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["name"]
    assert {"query", "execute_split", "merge"} <= {e["name"] for e in xs}
    assert doc["otherData"] == {"mode": "adaptive"}


def test_summary_table_lists_queries():
    tr = _traced_q1()
    table = obs_export.summary_table(tr)
    lines = table.splitlines()
    assert lines[0].startswith("query") and any("Q1" in ln for ln in lines)


def test_numpy_attrs_coerce_to_json(tmp_path):
    with tracing() as tr:
        tr.event("e", a=np.int64(3), b=np.array([1, 2]),
                 c=np.float32(0.5), d={"x", "y"})
    _, (span,) = obs_export.from_jsonl(
        obs_export.to_jsonl(tr, tmp_path / "t.jsonl"))
    assert span["attrs"] == {"a": 3, "b": [1, 2], "c": 0.5, "d": ["x", "y"]}


# ------------------------------ stream driver: spans + exact reconciliation
def test_run_stream_trace_reconciles_exactly(tmp_path):
    """sf=1 streamed run: the Chrome-exportable trace's per-query spans
    carry real_net_bytes equal to the driver's accounting, and the
    execution spans under each query sum to it EXACTLY."""
    stream = [runtime.StreamQuery(Q.build_query(qid), arrival=i * 0.004)
              for i, qid in enumerate(("Q1", "Q6", "Q12", "Q18"))]
    cfg = engine.EngineConfig(res=StorageResources(storage_power=0.25),
                              mode="adaptive")
    base = runtime.run_stream(stream, CAT, cfg)
    with tracing() as tr:
        run = runtime.run_stream(stream, CAT, cfg)
    for qid in run.results:
        assert_tables_identical(base.results[qid], run.results[qid], qid)

    (st,) = [t for t in tr.tree() if t["name"] == "run_stream"]
    assert st["attrs"]["real_net_bytes"] == run.real_net_bytes
    qnodes = {c["attrs"]["qid"]: c for c in st["children"]
              if c["name"] == "query"}
    assert set(qnodes) == set(run.per_query)
    for key, qn in qnodes.items():
        want = run.per_query[key]["real_net_bytes"]
        assert qn["attrs"]["real_net_bytes"] == want, key
        got = sum(c["attrs"]["shipped_bytes"] for c in qn["children"]
                  if c["name"] in ("storage_execute", "compute_replay"))
        assert got == want, key            # EXACT, not approximate
    # pushback transfers appear whenever requests were pushed back
    if run.n_pushback:
        assert tr.find("pushback_ship")
    # wave samples carry live load signals
    for ws in tr.find("wave_sample"):
        assert "exec_queue" in ws.attrs and "ship_queue" in ws.attrs
    # and the whole thing exports as a loadable Chrome trace
    doc = json.loads(open(obs_export.to_chrome_trace(
        tr, tmp_path / "stream.json")).read())
    assert len(doc["traceEvents"]) == len(tr.snapshot()) + 1


def test_run_stream_metrics_consistent():
    from repro.obs.metrics import get_metrics, set_metrics
    stream = [runtime.StreamQuery(Q.build_query(qid), arrival=i * 0.003)
              for i, qid in enumerate(("Q1", "Q6", "Q6"))]
    cfg = engine.EngineConfig(mode="adaptive")
    m = Metrics()
    prev = set_metrics(m)
    try:
        run = runtime.run_stream(stream, CAT, cfg)
    finally:
        set_metrics(prev)
    snap = m.snapshot()
    assert snap["counters"]["stream.requests.pushdown"] == run.n_pushdown
    assert snap["counters"].get("stream.requests.pushback", 0) \
        == run.n_pushback
    assert snap["counters"]["stream.net_bytes.real"] == run.real_net_bytes
    assert snap["histograms"]["stream.query_finish_s"]["count"] \
        == len(stream)
    assert any(k.startswith("stream.node") for k in snap["gauges"])


def test_engine_metrics_counters():
    from repro.obs.metrics import set_metrics
    m = Metrics()
    prev = set_metrics(m)
    try:
        run = engine.run_query(Q.build_query("Q6"), CAT,
                               engine.EngineConfig(mode="adaptive"))
    finally:
        set_metrics(prev)
    snap = m.snapshot()
    assert snap["counters"]["engine.queries"] == 1
    assert snap["counters"]["engine.requests.pushdown"] == run.n_admitted
    assert snap["counters"]["engine.net_bytes.real"] == run.real_net_bytes


# --------------------------------------------- bitmap via execute_split
def test_compute_side_bitmap_routes_through_execute_split():
    """Satellite: the Fig-4 batched path now runs under execute_split —
    same results as the per-partition oracle, with spans to prove the
    routing."""
    from repro.core import bitmap as bm
    from repro.queryproc import operators as ops
    from repro.queryproc.expressions import Col

    parts = [p.data for p in CAT.partitions_of("lineitem")][:4]
    pred = Col("l_quantity") <= 25
    out_cols = ("l_orderkey", "l_extendedprice")
    words = [ops.selection_bitmap(p, pred) for p in parts]
    with tracing() as tr:
        got = bm.compute_side_apply_batched(parts, words, out_cols)
    want = [ops.apply_bitmap(p.select(list(out_cols)), w)
            for p, w in zip(parts, words)]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert_tables_identical(g, w)
    es = tr.find("execute_split")
    assert es and es[0].attrs["n_pushdown"] == len(parts)
    assert tr.find("storage_execute")


# ------------------------------ crash-safe streaming export (JsonlStreamWriter)
def test_stream_writer_round_trip_merges_pairs(tmp_path):
    """Closed spans merge start+end (final dur + attrs), a span open at
    close-time reads back open (dur=None), writes after close are
    silently dropped."""
    path = tmp_path / "stream.jsonl"
    w = obs_export.JsonlStreamWriter(path, meta={"suite": "t"})
    tr = Tracer()
    tr.attach_sink(w)
    with tracing(tr):
        with tr.span("closed", qid="Q1") as sp:
            sp.set(late_attr=7)
            tr.event("ev", k=1)
        never = tr.start("never_closed")
    w.close()
    tr.end(never)                      # after close: dropped, no error
    meta, spans = obs_export.from_jsonl(path)
    assert meta["streaming"] is True and meta["suite"] == "t"
    by_name = {s["name"]: s for s in spans}
    assert by_name["closed"]["dur"] is not None
    assert by_name["closed"]["attrs"]["late_attr"] == 7   # end-side attrs won
    assert by_name["ev"]["dur"] == 0.0                    # events close too
    assert by_name["never_closed"]["dur"] is None         # still open on disk
    # the merged stream builds the same forest shape as the live tracer
    roots = obs_export.build_tree(spans)
    assert [r["name"] for r in roots] == ["closed", "never_closed"]
    assert [c["name"] for c in roots[0]["children"]] == ["ev"]


def test_stream_writer_tolerates_torn_tail(tmp_path):
    path = tmp_path / "stream.jsonl"
    with obs_export.JsonlStreamWriter(path) as w:
        tr = Tracer()
        tr.attach_sink(w)
        with tracing(tr):
            with tr.span("a"):
                pass
            with tr.span("b"):
                pass
    # simulate the process dying mid-write: chop the last line in half
    raw = path.read_bytes()
    path.write_bytes(raw[:len(raw) - 17])
    meta, spans = obs_export.from_jsonl(path)
    assert meta.get("streaming") is True
    names = [s["name"] for s in spans]
    assert "a" in names                 # the valid prefix survived
    a = next(s for s in spans if s["name"] == "a")
    assert a["dur"] is not None         # its end line landed before the tear


def test_stream_writer_survives_kill_dash_nine(tmp_path):
    """The satellite's contract end-to-end: a child process streaming a
    trace is SIGKILLed with spans open; the file left behind parses, the
    finished span has its dur, the in-flight spans read back open."""
    import signal
    import subprocess
    import sys
    import time

    path = tmp_path / "killed.jsonl"
    child = subprocess.Popen(
        [sys.executable, "-c", f"""
import sys, time
from repro.obs.trace import Tracer, tracing
from repro.obs.export import JsonlStreamWriter

w = JsonlStreamWriter({str(path)!r})
tr = Tracer()
tr.attach_sink(w)
with tracing(tr):
    with tr.span("finished", qid="Q1"):
        pass
    open_outer = tr.start("query", qid="Q9")
    open_inner = tr.start("storage_execute", parent=open_outer, node=0)
    print("SPANS_OPEN", flush=True)
    time.sleep(30)                     # killed long before this returns
"""],
        stdout=subprocess.PIPE, text=True, env={"PYTHONPATH": "src"},
        cwd="/root/repo")
    try:
        assert child.stdout.readline().strip() == "SPANS_OPEN"
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
    assert child.returncode == -signal.SIGKILL
    meta, spans = obs_export.from_jsonl(path)
    assert meta.get("streaming") is True
    by_name = {s["name"]: s for s in spans}
    assert by_name["finished"]["dur"] is not None
    assert by_name["query"]["dur"] is None
    assert by_name["storage_execute"]["dur"] is None
    assert by_name["storage_execute"]["parent"] == by_name["query"]["sid"]
    assert by_name["query"]["attrs"]["qid"] == "Q9"


def test_stream_writer_matches_batch_export_shape(tmp_path):
    """Streaming a real engine run produces the same forest as the batch
    exporter over the same tracer — the crash-safe path loses nothing."""
    tr = Tracer()
    w = obs_export.JsonlStreamWriter(tmp_path / "live.jsonl")
    tr.attach_sink(w)
    with tracing(tr):
        engine.run_query(Q.build_query("Q6"), CAT,
                         engine.EngineConfig(mode="adaptive"))
    w.close()
    obs_export.to_jsonl(tr, tmp_path / "batch.jsonl")
    _, live = obs_export.from_jsonl(tmp_path / "live.jsonl")
    _, batch = obs_export.from_jsonl(tmp_path / "batch.jsonl")
    assert obs_export.build_tree(live) == obs_export.build_tree(batch)
