"""The on-chip benchmark's harness (``bench/``), checked without the chip.

Covers what the benchmark owns: loading cells, traffic mixes and metric
readers by name; the metric arithmetic; the reduction of a profiler
trace recorded on a TPU v5e; the refusal to run off the chip or outside
a checkout; and the check that decides ``correct``: two concurrent
``run_stream`` clients on the process storage tier agree with the plain
reference, while the float32 control and a timed path broken underneath
(an answer altered where it is produced, half of the requests left out)
read as not correct. Everything runs at a tiny scale on the CPU.
"""
import dataclasses
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import compare, datagen, harness, reference, trace_reduce  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
RECORDED_TRACE = os.path.join(ROOT, "bench", "testdata",
                              "sf1-scan_trace_events.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def manifest():
    with open(MANIFEST) as f:
        return json.load(f)


# ------------------------------------------------------- loading by name
def test_every_cell_loads_its_config_traffic_and_readers():
    man = manifest()
    for w in man["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"] == 1
        assert cell.config["name"] == w["config"]
        assert cell.streams and all(cell.streams)
        assert set(cell.queries) <= set(reference.QUERIES)
        for traced in (False, True):
            assert cell.metrics[traced]
            for m in cell.metrics[traced]:
                assert callable(harness.metric_reader(m["name"]))
    for c in man["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) <= set(cfg) and set(c["reduced"]) == set(
            cfg["reduced"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)


def _streams(traffic):
    with open(os.path.join(ROOT, "bench", "traffic", traffic + ".json")) as f:
        return harness.stream_orders(json.load(f))


def test_stream_orders_follow_tpch_appendix_a():
    with open(os.path.join(ROOT, "bench", "streams",
                           "tpch-appendix-a.json")) as f:
        table = json.load(f)["streams"]
    for seq in table.values():
        assert sorted(seq) == list(range(1, 23))
    assert _streams("joins-2streams") == [["Q3", "Q18", "Q5", "Q10"],
                                          ["Q10", "Q5", "Q18", "Q3"]]
    assert _streams("scan-2streams") == [["Q6", "Q1"]] * 2
    assert _streams("power") == [["Q14", "Q6", "Q17", "Q18", "Q8", "Q3",
                                  "Q22", "Q4", "Q15", "Q1", "Q10", "Q19",
                                  "Q5", "Q7", "Q12"]]


def test_generator_follows_the_tpch_data_rules():
    t = datagen.generate(0.01, 5)
    od, li, pa = t["orders"], t["lineitem"], t["part"]
    okey = od["o_orderkey"].astype(np.int64)
    assert len(okey) == 15_000 and okey[0] == 1
    assert np.all(np.diff(okey) > 0) and np.all(okey % 32 < 8)
    assert np.all(od["o_custkey"] % 3 != 0)
    # lineitem: 1 to 7 lines per order, written in order-key order
    assert np.all(np.diff(li["l_orderkey"]) >= 0)
    counts = np.bincount(np.searchsorted(okey, li["l_orderkey"]),
                         minlength=len(okey))
    assert counts.min() == 1 and counts.max() == 7
    # each line's supplier is one of its part's four partsupp suppliers
    ps = set(zip(t["partsupp"]["ps_partkey"].tolist(),
                 t["partsupp"]["ps_suppkey"].tolist()))
    assert set(zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist())) <= ps
    price = pa["p_retailprice"][li["l_partkey"] - 1]
    assert np.allclose(li["l_extendedprice"], li["l_quantity"] * price)
    cur = datagen.CURRENTDATE
    flag, rd = li["l_returnflag"], li["l_receiptdate"]
    assert set(flag[rd <= cur]) == {datagen.FLAG_A, datagen.FLAG_R}
    assert set(flag[rd > cur]) == {datagen.FLAG_N}
    assert np.array_equal(li["l_linestatus"] == datagen.STATUS_O,
                          li["l_shipdate"] > cur)
    total = np.zeros(len(okey))
    np.add.at(total, np.searchsorted(okey, li["l_orderkey"]),
              li["l_extendedprice"] * (1 + li["l_tax"])
              * (1 - li["l_discount"]))
    assert np.allclose(od["o_totalprice"], total, atol=0.005 * 7)
    assert np.array_equal(t["nation"]["n_regionkey"][[2, 6, 8, 12]],
                          [1, 3, 2, 2])     # BRAZIL, FRANCE, INDIA, JAPAN


def test_seed_places_partitions_and_keeps_every_row_in_place():
    cfg = {**_tiny().config}
    tables = datagen.tables_for(cfg)
    a = datagen.load_catalog(tables, cfg, 2**40 + 1)
    b = datagen.load_catalog(tables, cfg, 7)
    again = datagen.load_catalog(tables, cfg, 7)
    for name, parts in a.tables.items():
        for pa, pb in zip(parts, b.tables[name]):
            for c, v in pa.data.cols.items():
                assert np.array_equal(v, pb.data.cols[c])
        on = [sum(p.node_id == n for p in parts) for n in range(2)]
        assert max(on) - min(on) <= 1
    nodes = [[p.node_id for p in c.tables["lineitem"]] for c in (a, b, again)]
    assert nodes[0] != nodes[1] and nodes[1] == nodes[2]
    assert sorted((p.table, p.index) for n in b.nodes for p in n.partitions) \
        == sorted((p.table, p.index) for ps in b.tables.values() for p in ps)
    assert all(p.node_id == n.node_id for n in b.nodes for p in n.partitions)
    assert not a.clustered


def test_key_skew_and_clustering_come_from_the_configuration():
    flat = datagen.generate(0.01, 5)["lineitem"]["l_partkey"]
    skewed = datagen.generate(0.01, 5, {"l_partkey": 1.2})["lineitem"]
    top = np.sort(np.bincount(skewed["l_partkey"]))[::-1]
    assert top[0] > 20 * np.bincount(flat).max()
    assert skewed["l_partkey"].min() >= 1
    assert skewed["l_partkey"].max() <= 2_000
    cfg = {**_tiny().config, "cluster": {"lineitem": "l_orderkey"}}
    cat = datagen.load_catalog(datagen.tables_for(cfg), cfg, 3)
    assert cat.clustered == {"lineitem": "l_orderkey"}
    ranges = [(p.data.cols["l_orderkey"].min(), p.data.cols["l_orderkey"].max())
              for p in cat.tables["lineitem"]]
    assert all(hi < lo for (_l, hi), (lo, _h) in zip(ranges, ranges[1:]))


# ---------------------------------------------------- metric arithmetic
def _window(lat_ok, late=(), errors=0, seconds=10.0):
    recs = [harness.Record(0, i, "Q1", 0.0, t) for i, t in enumerate(lat_ok)]
    recs += [harness.Record(2, 99, "Q1", seconds - 1.0, seconds + t)
             for t in late]
    recs += [harness.Record(1, i, "Q1", 0.0, 1.0, error="boom")
             for i in range(errors)]
    return harness.Window(0.0, seconds, recs, {})


def test_geomean_and_p95_over_all_samples():
    assert harness.geomean([1.0, 4.0]) == pytest.approx(2.0)
    assert harness.geomean([0.5] * 7) == pytest.approx(0.5)
    xs = list(range(1, 101))
    assert harness.p95(xs) == pytest.approx(95.05)
    assert harness.p95([3.0]) == 3.0


def test_end_to_end_readers_count_only_answers_inside_the_window():
    ctx = harness.Ctx(_window([1.0, 2.0, 4.0, 8.0], late=[0.5], errors=2),
                      setup_s=12.5)
    read = {m: harness.metric_reader(m) for m in
            ("qps", "latency_geomean_s", "latency_p95_s", "setup_s")}
    assert read["qps"](ctx) == pytest.approx(4 / 10.0)
    assert read["latency_geomean_s"](ctx) == pytest.approx(
        (1 * 2 * 4 * 8) ** 0.25)
    assert read["latency_p95_s"](ctx) == pytest.approx(
        np.percentile([1.0, 2.0, 4.0, 8.0], 95))
    assert read["setup_s"](ctx) == 12.5


def test_span_readers_normalise_per_call():
    win = _window([1.0, 2.0])
    win.spans = [("plan_requests", 0.0, 0.010), ("arbitrate", 0.0, 0.030),
                 ("storage_execute", 0.0, 0.5), ("storage_execute", 0.1, 0.2),
                 ("compute_replay", 0.0, 0.2), ("residual_compute", 0, 0.4)]
    for r, b in zip(win.records, (3_000_000, 1_000_000)):
        r.net_bytes = b
    ctx = harness.Ctx(win, 0.0)
    want = {"plan_ms": 20.0, "storage_exec_ms": 300.0, "replay_ms": 100.0,
            "residual_ms": 200.0, "shipped_mb": 2.0}
    for name, v in want.items():
        assert harness.metric_reader(name)(ctx) == pytest.approx(v), name
    assert harness.metric_reader("device_busy_ms")(ctx) is None
    assert harness.metric_reader("device_idle_share")(ctx) is None


# ------------------------------------------------------ trace reduction
def test_union_and_gaps_on_known_intervals():
    busy = trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 12)])
    assert busy == [(0, 4), (5, 7), (9, 12)]
    assert trace_reduce.clip(busy, 1, 10) == [(1, 4), (5, 7), (9, 10)]
    assert trace_reduce.gaps(trace_reduce.clip(busy, 1, 10), 1, 10) == [
        (4, 5), (7, 9)]
    assert trace_reduce.gaps([], 0, 3) == [(0, 3)]
    ev = trace_reduce.Events(
        {"/device:TPU:0": [("a", 10, 5), ("b", 12, 10), ("a", 40, 10)]},
        [("bench_query:0:0:Q1", 0, 30), ("bench_query:1:0:Q6", 20, 40)])
    red = trace_reduce.reduce(ev)
    assert red.window_s == pytest.approx(60e-9)
    assert red.busy_s == pytest.approx(22e-9)
    assert red.top_ops == [("a", pytest.approx(15e-9)),
                           ("b", pytest.approx(10e-9))]
    assert red.gaps == [(22, 40), (0, 10), (50, 60)]   # longest first
    labels = trace_reduce.label_gaps(
        red, ev, [("storage_execute", 25e-9, 35e-9)], offset_ns=0.0)
    assert labels[0] == ("Q6 storage_execute", pytest.approx(18e-9))
    assert [lb for lb, _s in labels[1:]] == ["Q1", "Q6"]


def test_ops_are_named_by_program_and_instruction():
    ops = [("%fusion.3 = f32[8] fusion(f32[8] %p), kind=kLoop", 12.0, 2.0),
           ("%copy.1 = s32[4] copy(s32[4] %x)", 30.0, 1.0),
           ("%sort.2", 55.0, 1.0)]
    mods = [("jit_stage_fn(7)", 50.0, 10.0), ("jit_stage_fn(1)", 10.0, 10.0)]
    assert [n for n, _t, _d in trace_reduce._op_names(ops, mods)] == [
        "jit_stage_fn(1)/%fusion.3", "?/%copy.1", "jit_stage_fn(7)/%sort.2"]


def test_extract_finds_annotations_of_every_client_thread(tmp_path):
    """Host threads share a line name in the trace; the annotation of
    each client has to be found all the same."""
    import threading
    import time

    import jax

    jax.profiler.start_trace(str(tmp_path))

    def call(k):
        with harness._annotation(f"{trace_reduce.ANNOTATION}{k}:0:Q6"):
            time.sleep(0.02)

    threads = [threading.Thread(target=call, args=(k,)) for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jax.profiler.stop_trace()
    ev = trace_reduce.extract(str(tmp_path))
    assert sorted(a[0] for a in ev.annotations) == [
        f"{trace_reduce.ANNOTATION}{k}:0:Q6" for k in range(3)]


def _sweep_busy(intervals, lo, hi):
    """Busy time by an overlap count over sorted endpoints: a second way
    to the union's length."""
    pts = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                 + [(min(e, hi), -1) for s, e in intervals
                    if e > lo and s < hi])
    busy, depth, last = 0.0, 0, lo
    for t, d in pts:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_reduction_of_a_trace_recorded_on_the_chip():
    ev = trace_reduce.load(RECORDED_TRACE)
    assert ev.ops and ev.annotations
    red = trace_reduce.reduce(ev)
    lo, hi = trace_reduce.window(ev)
    (dev, ops), = ev.ops.items()
    want = _sweep_busy([(t, t + d) for _n, t, d in ops], lo, hi)
    assert red.devices == 1
    assert red.busy_s == pytest.approx(want * 1e-9, rel=1e-12)
    assert 0 < red.busy_s < red.window_s == pytest.approx((hi - lo) * 1e-9)
    idle = sum(e - s for s, e in red.gaps) * 1e-9
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-9)
    secs = [s for _n, s in red.top_ops]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) >= red.busy_s * (1 - 1e-9)


# ------------------------------------------------------------ the command
def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         manifest()["workloads"][0]["name"], "--seed",
         str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _result_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_command_refuses_a_machine_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert not _result_lines(p.stdout)
    assert "no TPU" in p.stderr


def test_command_refuses_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(MANIFEST, tmp_path / "BENCHMARK.json")
    for path in manifest()["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not _result_lines(p.stdout)


# ------------------------------------------------------ deciding correct
def _tiny():
    """The first cell, its configuration cut to a CPU test's size, under
    the two-stream joins traffic."""
    cell = harness.load_cell(manifest()["workloads"][0]["name"])
    return dataclasses.replace(
        cell, config={**cell.config, "tpch_scale_factor": 0.005,
                      "rows_per_partition": 1_000},
        streams=_streams("joins-2streams"))


def _drive(cell, seed=2**35 + 9, seconds=1.5):
    """A run's path from set-up to the verdict, without the device check."""
    served = harness.build(cell, seed)
    try:
        harness.warm(served)
        win = harness.serve_window(served, seconds, traced=False)
    finally:
        harness.free(served)
    return win, harness.judge(served, win)


def test_two_concurrent_clients_agree_with_the_reference():
    win, verdict = _drive(_tiny())
    assert {r.client for r in win.completed} == {0, 1}
    assert {r.qid for r in win.records} == {"Q3", "Q5", "Q10", "Q18"}
    assert verdict.correct, verdict.numbers()
    assert verdict.compared == len(win.records) and verdict.wrong == 0
    assert win.counters["residual.jit_cache.misses"] == 0
    assert win.counters["residual.fallbacks"] == 0


def test_float32_control_fails_and_float64_passes():
    tables = datagen.tables_for(_tiny().config)
    queries = _streams("power")[0]
    limits = compare.load_limits()
    want = {q: reference.run(q, tables) for q in queries}
    for ft, ok in ((np.float64, True), (np.float32, False)):
        answers = [(q, reference.run(q, tables, ft).cols) for q in queries]
        v = compare.judge(answers, want, 0, limits)
        assert v.correct is ok, (ft, v.numbers())
    ctl = compare.judge([(q, reference.run(q, tables, np.float32).cols)
                         for q in ("Q3", "Q5", "Q10", "Q18")], want, 0, limits)
    assert ctl.max_rel_err > 3 * limits["max_rel_err"] or ctl.wrong


def _alter_answer(monkeypatch):
    from repro.core import runtime
    orig = runtime.run_residual

    def altered(query, merged, backend=None):
        table, info = orig(query, merged, backend)
        cols = dict(table.cols)
        for c, v in cols.items():
            if v.dtype.kind == "f" and len(v):
                v = v.copy()
                v[0] *= 1 + 1e-6
                cols[c] = v
                break
        return type(table)(cols), info

    monkeypatch.setattr(runtime, "run_residual", altered)


def _drop_half(monkeypatch):
    from repro.core import engine
    orig = engine.plan_requests

    def half(*a, **kw):
        return orig(*a, **kw)[::2]

    monkeypatch.setattr(engine, "plan_requests", half)


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half],
                         ids=["answer_altered", "half_the_requests_left_out"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    _win, verdict = _drive(_tiny())
    assert not verdict.correct, verdict.numbers()
