"""The cell loop: set-up, the measured window, the check, the metrics.

A cell of ``BENCHMARK.json`` names a configuration (its file holds the
deployment: data scale, storage nodes, storage power, engine settings)
and a traffic mix (``bench/traffic/<name>.json``: how many TPC-H query
streams, which of TPC-H's Appendix A orders, which queries). The traffic
is a closed loop: one client thread per stream calls
``runtime.run_stream([StreamQuery(q)])`` for its next query as soon as
the previous answer returned. A query's latency is this module's clock
around that call, so planning and arbitration are in it.

Set-up builds the data and the catalog, spawns the storage workers and
ships them their partitions, runs each of the cell's queries once (the
tensor residual's observe pass) and once more (its jitted stages, loaded
from the persistent compilation cache or compiled), and, with more than
one client, one warm-up round of the whole mix. The window then runs for
``seconds``. Queries still running at its close are waited for and
checked, but only the queries completed inside the window enter the
metrics. After the window the program's state is freed and every answer
is compared with the plain reference (``bench/compare.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import compare, datagen, reference, trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
STRAGGLER_WAIT_S = 120.0     # how long past the close an answer may come


# ----------------------------------------------------------------- cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    streams: List[List[str]]     # per client, its queries in order
    metrics: Dict[bool, List[Dict]]  # traced? -> metric entries

    @property
    def queries(self) -> List[str]:
        """The cell's query ids, in order of first appearance."""
        seen: List[str] = []
        for s in self.streams:
            seen += [q for q in s if q not in seen]
        return seen


def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def stream_orders(traffic: Dict) -> List[List[str]]:
    """Each client's queries: the stream orders of ``traffic["order"]``
    (a file under ``bench/streams``) for its ``streams``, restricted to
    its ``queries``."""
    table = _read_json(os.path.join(BENCH_DIR, "streams",
                                    traffic["order"] + ".json"))["streams"]
    wanted = {q.upper() for q in traffic["queries"]}
    out = []
    for k in traffic["streams"]:
        seq = [f"Q{n}" for n in table[str(k)] if f"Q{n}" in wanted]
        if set(seq) != wanted:
            raise ValueError(f"stream {k} of {traffic['order']} lacks "
                             f"{sorted(wanted - set(seq))}")
        out.append(seq)
    return out


def load_cell(name: str, manifest_path: Optional[str] = None) -> Cell:
    man = _read_json(manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = _read_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _read_json(os.path.join(BENCH_DIR, "traffic",
                                      w["traffic"] + ".json"))

    def mine(m: Dict) -> bool:
        return name in m.get("workloads", (name,))

    metrics = {False: [m for m in man["end_to_end"] if mine(m)],
               True: [m for m in man["per_layer"] if mine(m)]}
    return Cell(name, w["chips"], config, traffic, stream_orders(traffic),
                metrics)


def metric_reader(name: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- set-up
@dataclasses.dataclass
class Served:
    """The system under test, set up for one cell."""
    cell: Cell
    tables: datagen.Tables
    catalog: object
    cfg: object
    queries: Dict[str, object]
    setup_parts: Dict[str, float]


def engine_config(config: Dict):
    from repro.core.cost import StorageResources
    from repro.core.engine import EngineConfig
    res = StorageResources(**config["storage_node"],
                           storage_power=config["storage_power"])
    return EngineConfig(res=res, **config["engine"])


def run_one(served: Served, qid: str):
    """One client call: the query alone in a ``run_stream``."""
    from repro.core import runtime
    return runtime.run_stream(
        [runtime.StreamQuery(served.queries[qid], arrival=0.0)],
        served.catalog, served.cfg)


def build(cell: Cell, seed: int) -> Served:
    from repro.compiler import compile_query
    from repro.distributed import workers

    parts: Dict[str, float] = {}
    t0 = time.perf_counter()
    tables = datagen.tables_for(cell.config)
    catalog = datagen.load_catalog(tables, cell.config, seed)
    parts["catalog_s"] = time.perf_counter() - t0
    cfg = engine_config(cell.config)
    t0 = time.perf_counter()
    workers.pool_for(catalog, pd_slots=cfg.res.pd_slots)
    parts["workers_s"] = time.perf_counter() - t0
    queries = {q: compile_query(q) for q in cell.queries}
    return Served(cell, tables, catalog, cfg, queries, parts)


def warm(served: Served) -> None:
    """Observe pass, jitted stages, and the warm-up round."""
    parts = served.setup_parts
    for phase in ("observe_s", "jit_load_s"):
        t0 = time.perf_counter()
        for qid in served.cell.queries:
            run_one(served, qid)
        parts[phase] = time.perf_counter() - t0
    t0 = time.perf_counter()
    if len(served.cell.streams) > 1:
        clients(served, time.perf_counter() + 1e9, one_round=True)
    parts["warmup_round_s"] = time.perf_counter() - t0


# ---------------------------------------------------------------- window
@dataclasses.dataclass
class Record:
    client: int
    n: int
    qid: str
    t0: float
    t1: float = 0.0
    cols: Optional[Dict[str, np.ndarray]] = None
    net_bytes: int = 0
    error: Optional[str] = None


def clients(served: Served, deadline: float, one_round: bool = False,
            annotate: bool = False) -> List[Record]:
    """Run every client's closed loop until ``deadline`` (or through its
    sequence once, with ``one_round``); returns every call's record."""
    records: List[Record] = []
    lock = threading.Lock()

    def loop(k: int, seq: List[str]) -> None:
        n = 0
        while time.perf_counter() < deadline and not (
                one_round and n == len(seq)):
            qid = seq[n % len(seq)]
            rec = Record(k, n, qid, time.perf_counter())
            ann = (_annotation(f"{trace_reduce.ANNOTATION}{k}:{n}:{qid}")
                   if annotate else contextlib.nullcontext())
            try:
                with ann:
                    run = run_one(served, qid)
                rec.t1 = time.perf_counter()
                rec.cols = {c: np.asarray(v) for c, v in
                            run.results[qid].cols.items()}
                rec.net_bytes = int(run.real_net_bytes)
            except Exception as e:  # noqa: BLE001 — a failed answer
                rec.t1 = time.perf_counter()
                rec.error = repr(e)
            with lock:
                records.append(rec)
            n += 1

    threads = [threading.Thread(target=loop, args=(k, s), daemon=True)
               for k, s in enumerate(served.cell.streams)]
    for t in threads:
        t.start()
    wait_until = max(deadline, time.perf_counter()) + STRAGGLER_WAIT_S
    for t in threads:
        t.join(timeout=max(0.0, wait_until - time.perf_counter()))
    with lock:
        late = [t for t in threads if t.is_alive()]
        if late:
            records.append(Record(-1, -1, "", deadline, deadline,
                                  error=f"{len(late)} client(s) never "
                                        "returned"))
        return list(records)


def _annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    t_start: float
    seconds: float
    records: List[Record]
    counters: Dict[str, float]                 # program counters' deltas
    spans: Optional[List] = None               # repro.obs spans (traced)
    events: Optional[trace_reduce.Events] = None

    @property
    def t_end(self) -> float:
        return self.t_start + self.seconds

    @property
    def completed(self) -> List[Record]:
        """Answers that came back inside the window."""
        return [r for r in self.records
                if r.error is None and r.t1 <= self.t_end]


COUNTERS = ("residual.jit_cache.misses", "residual.jit_cache.hits",
            "residual.fallbacks", "residual.errors", "residual.observes")


def serve_window(served: Served, seconds: float, traced: bool,
                 trace_dir: Optional[str] = None) -> Window:
    from repro.obs import trace as obs_trace
    from repro.obs.metrics import get_metrics

    m = get_metrics()
    before = {c: m.counter(c).value for c in COUNTERS}
    tracer = None
    if traced:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracer = obs_trace.Tracer()
        obs_trace.set_tracer(tracer)
    t_start = time.perf_counter()
    try:
        records = clients(served, t_start + seconds, annotate=traced)
    finally:
        if traced:
            obs_trace.set_tracer(None)
            import jax
            jax.profiler.stop_trace()
    win = Window(t_start, seconds, records,
                 {c: m.counter(c).value - before[c] for c in COUNTERS})
    if traced:
        win.spans = [(s.name, tracer.t0 + s.t0, tracer.t0 + s.t0 + s.dur)
                     for s in tracer.snapshot() if s.dur is not None]
        win.events = trace_reduce.extract(trace_dir)
    return win


# --------------------------------------------------------------- metrics
@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""
    window: Window
    setup_s: float
    device: Optional[trace_reduce.Reduction] = None

    @property
    def latencies(self) -> List[float]:
        return [r.t1 - r.t0 for r in self.window.completed]

    @property
    def calls(self) -> List[Record]:
        """Every call of the traced stretch: all started in the window,
        each traced whole."""
        return [r for r in self.window.records if r.client >= 0]

    def span_seconds(self, *names: str) -> Optional[float]:
        if self.window.spans is None:
            return None
        return sum(t1 - t0 for n, t0, t1 in self.window.spans if n in names)

    def per_call_ms(self, seconds: Optional[float]) -> Optional[float]:
        if seconds is None or not self.calls:
            return None
        return 1e3 * seconds / len(self.calls)


def geomean(xs) -> float:
    return float(np.exp(np.mean(np.log(np.asarray(xs, np.float64)))))


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def read_metrics(cell: Cell, traced: bool, ctx: Ctx) -> Dict[str, Dict]:
    out = {}
    for m in cell.metrics[traced]:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


# --------------------------------------------------------------- the run
def device_info() -> Dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def free(served: Served) -> None:
    from repro.distributed import workers
    workers.close_all_pools()
    served.catalog = None
    served.queries = {}


def judge(served: Served, win: Window) -> compare.Verdict:
    """Every answer of the window, stragglers included, against the
    reference; call after ``free``."""
    answers = [(r.qid, r.cols) for r in win.records if r.error is None]
    want = {q: reference.run(q, served.tables)
            for q in sorted({q for q, _ in answers})}
    failed = sum(1 for r in win.records if r.error is not None)
    return compare.judge(answers, want, failed, compare.load_limits())


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_process: float, log: Callable[[str], None]) -> Dict:
    """One run of ``cell``: returns the result line's object. ``log``
    takes the earlier output lines."""
    served = build(cell, seed)
    warm(served)
    setup_s = time.perf_counter() - t_process
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        win = serve_window(served, seconds, traced, trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    peak = memory_peak_bytes()
    log("bench-setup " + json.dumps({
        "setup_s": setup_s, **served.setup_parts,
        "window_counters": win.counters, "memory_peak_bytes": peak,
        "started": len(win.records), "completed": len(win.completed)}))
    free(served)
    t0 = time.perf_counter()
    verdict = judge(served, win)
    log("bench-check " + json.dumps({
        "reference_s": time.perf_counter() - t0,
        "first_wrong": verdict.first_wrong}))
    device = device_info()
    device["memory_peak_bytes"] = peak
    ctx = Ctx(win, setup_s)
    result: Dict = {"correct": verdict.correct,
                    "attempted": len(win.records),
                    "failed": verdict.failed + verdict.wrong}
    if traced:
        red = trace_reduce.reduce(win.events)
        ctx.device = red
        if red is not None:
            offset = trace_reduce.clock_offset_ns(win.events, {
                f"{trace_reduce.ANNOTATION}{r.client}:{r.n}:{r.qid}": r.t0
                for r in ctx.calls})
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            result["breakdown"] = {
                "device_ops": [[n, s] for n, s in red.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in trace_reduce.label_gaps(
                    red, win.events, win.spans, offset)]}
    result["metrics"] = read_metrics(cell, traced, ctx)
    result["device"] = device
    result["compared"] = verdict.numbers()
    return result
