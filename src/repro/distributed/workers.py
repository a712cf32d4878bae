"""Multi-process storage tier: real worker processes behind a wire codec.

``runtime.run_stream`` historically *simulated* storage nodes as thread
pools inside one process — the Arbitrator reacted to simulator slot
counts, not actual storage-side pressure. This module splits the storage
layer into real **storage-worker processes** (one per catalog node, spawned
``multiprocessing`` children talking over a socketpair and pinned off the
accelerator), each owning the
disjoint partition set of its node:

- the compute layer dispatches compiled ``PushPlan``s **over the wire**
  (a small length-prefixed codec: u32 frame length | u32 header length |
  JSON header | raw body — ColumnTable columns travel as raw dtype/shape
  tagged buffers, plan specs as a marshal-backed pickle that survives the
  lambdas in ``derive`` tuples);
- pushback fetches the raw accessed-column projection as **real
  serialized bytes** (``fetch_projection``), so the transfer is an actual
  inter-process copy, not an in-heap view;
- every worker response carries a live load snapshot (queue depth,
  in-flight, CPU occupancy) that the pool publishes into the very
  ``stream.node<N>.exec_queue``/``ship_queue`` gauges the Arbitrator's
  ``MeasuredLoad`` polls — per-worker admission control reacting to real
  storage-side pressure (``burn()`` injects that pressure for the
  decision-shift benchmark);
- worker-side spans ride back in the response and are adopted into the
  compute-side trace under the dispatching span (span-id handoff:
  requests carry the parent span's ``sid``, worker records echo it as
  ``remote_parent``);
- a dead channel (worker SIGKILL -> EOF) or an overdue request surfaces
  as :class:`core.faults.WorkerFault` (``crash``/``timeout``) and flows
  through the existing retry -> deadline -> demote-to-pushback recovery
  machinery — the fault domain moved from injected schedules to real
  process failure, and recovery stays byte-identical (demotion replays
  from the parent's catalog copy: the durable-store tier is outside the
  storage fault domain, per the PR-8 contract).

``EngineConfig.storage_tier="process"`` routes execution through a pool;
``"inproc"`` (the default) is the oracle — all 15 queries are
byte-identical across tiers for any decision vector and fault schedule
(tests/test_workers.py). See docs/distributed.md for the wire protocol
and the load-signal schema.
"""
from __future__ import annotations

import atexit
import hashlib
import io
import itertools
import json
import marshal
import multiprocessing
import os
import pickle
import queue
import signal
import socket
import struct
import sys
import threading
import time
import types
from concurrent.futures import Future, TimeoutError as FutTimeout
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import faults as _faults
from repro.core.executor import (EXECUTOR_REFERENCE, CompiledPushPlan,
                                 compile_push_plan)
from repro.core.plan import execute_push_plan
from repro.obs import trace as obs_trace
from repro.obs.metrics import get_metrics
from repro.queryproc.table import ColumnTable

__all__ = ["WorkerPool", "pool_for", "close_all_pools",
           "encode_plan", "decode_plan"]

_U32 = struct.Struct("<I")
_STARTUP_TIMEOUT_S = 120.0   # spawn -> first served request


# ------------------------------------------------------------- wire framing
def _write_frame(sock: socket.socket, header: Dict, body: bytes = b"") -> int:
    """One length-prefixed frame: u32 total | u32 hlen | header | body.
    Returns the bytes written (the wire-byte accounting unit)."""
    h = json.dumps(header, separators=(",", ":")).encode("utf-8")
    frame = b"".join((_U32.pack(4 + len(h) + len(body)), _U32.pack(len(h)),
                      h, body))
    sock.sendall(frame)
    return len(frame)


def _read_exact(sock: socket.socket, n: int) -> bytearray:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        k = sock.recv_into(view[got:], n - got)
        if k == 0:
            raise EOFError("channel closed")
        got += k
    return buf


def _read_frame(sock: socket.socket) -> Tuple[Dict, memoryview, int]:
    """Returns (header, body view, total frame bytes)."""
    total = _U32.unpack(bytes(_read_exact(sock, 4)))[0]
    payload = _read_exact(sock, total)
    hlen = _U32.unpack(bytes(payload[:4]))[0]
    header = json.loads(bytes(payload[4:4 + hlen]).decode("utf-8"))
    return header, memoryview(payload)[4 + hlen:], 4 + total


# ------------------------------------------------------- value/table codec
class _Cursor:
    """Sequential reader over a frame body (buffers decode in the order
    they were appended by ``_enc``)."""

    def __init__(self, body):
        self.body = memoryview(body)
        self.off = 0

    def take(self, n: int) -> memoryview:
        v = self.body[self.off:self.off + n]
        self.off += n
        return v


def _enc_arr(a: np.ndarray, bufs: List[bytes]) -> Dict:
    a = np.ascontiguousarray(a)
    raw = a.tobytes()
    bufs.append(raw)
    return {"!": "nd", "d": a.dtype.str, "s": list(a.shape), "n": len(raw)}


def _enc(v, bufs: List[bytes]):
    """Encode a value tree into a JSON-able header structure + raw body
    buffers. Covers everything a push-plan result/aux can hold: scalars,
    numpy arrays, ColumnTables, and (possibly nested) list/tuple/dict."""
    if v is None or isinstance(v, (bool, str)):
        return v
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, (float, np.floating)):
        return float(v)
    if isinstance(v, np.ndarray):
        return _enc_arr(v, bufs)
    if isinstance(v, ColumnTable):
        return {"!": "ct",
                "c": [[c, _enc_arr(v.cols[c], bufs)] for c in v.columns]}
    if isinstance(v, tuple):
        return {"!": "tu", "v": [_enc(x, bufs) for x in v]}
    if isinstance(v, list):
        return {"!": "li", "v": [_enc(x, bufs) for x in v]}
    if isinstance(v, dict):
        return {"!": "di",
                "v": [[_enc(k, bufs), _enc(x, bufs)] for k, x in v.items()]}
    raise TypeError(f"not wire-encodable: {type(v).__name__}")


def _dec_arr(spec: Dict, cur: _Cursor) -> np.ndarray:
    raw = cur.take(spec["n"])
    # frombuffer over the received bytearray: writable, zero extra copies
    return np.frombuffer(raw, dtype=np.dtype(spec["d"])).reshape(spec["s"])


def _dec(v, cur: _Cursor):
    if isinstance(v, dict):
        t = v["!"]
        if t == "nd":
            return _dec_arr(v, cur)
        if t == "ct":
            return ColumnTable({c: _dec_arr(s, cur) for c, s in v["c"]})
        if t == "tu":
            return tuple(_dec(x, cur) for x in v["v"])
        if t == "li":
            return [_dec(x, cur) for x in v["v"]]
        if t == "di":
            return {_dec(k, cur): _dec(x, cur) for k, x in v["v"]}
        raise TypeError(f"unknown wire tag {t!r}")
    return v


# ---------------------------------------------------------- PushPlan codec
def _rebuild_fn(code_b: bytes, module: str, name: str, defaults,
                closure_vals):
    """Reconstruct a (possibly lambda) function from its marshalled code
    object, rebound to its defining module's globals on the receiving
    side (the worker imports the same code, so ``np`` etc. resolve)."""
    code = marshal.loads(code_b)
    try:
        import importlib
        g = importlib.import_module(module).__dict__
    except Exception:  # noqa: BLE001 — fall back to a numpy-bearing scope
        g = {"np": np, "__builtins__": __builtins__}
    cells = None
    if closure_vals is not None:
        cells = tuple(types.CellType(v) for v in closure_vals)
    return types.FunctionType(code, g, name, defaults, cells)


class _PlanPickler(pickle.Pickler):
    """Pickler whose function reducer marshals ``__code__`` — the
    ``derive`` entries of real query plans are lambdas (not plain
    picklable); Expr trees and the PushPlan dataclass pickle normally."""

    def reducer_override(self, obj):
        if isinstance(obj, types.FunctionType):
            try:
                import importlib
                mod = importlib.import_module(obj.__module__)
                if getattr(mod, obj.__qualname__, None) is obj:
                    return NotImplemented   # importable by name: pickle as
                    #   the usual global ref (also breaks the recursion on
                    #   _rebuild_fn itself)
            except Exception:  # noqa: BLE001 — fall through to marshal
                pass
            try:
                code = marshal.dumps(obj.__code__)
            except ValueError:
                return NotImplemented
            closure = None
            if obj.__closure__:
                vals = []
                for cell in obj.__closure__:
                    try:
                        vals.append(cell.cell_contents)
                    except ValueError:
                        vals.append(None)
                closure = tuple(vals)
            return (_rebuild_fn, (code, obj.__module__ or "builtins",
                                  obj.__name__, obj.__defaults__, closure))
        return NotImplemented


def encode_plan(plan) -> bytes:
    buf = io.BytesIO()
    _PlanPickler(buf, protocol=5).dump(plan)
    return buf.getvalue()


def decode_plan(spec: bytes):
    return pickle.loads(spec)


# ----------------------------------------------------------- worker process
def _pin_off_accelerator() -> None:
    """Keep a storage worker on the host: it runs numpy plans only, and a
    chip belongs to one process — the compute process that holds it. The
    worker's JAX (imported with the package) may then only ever bring up
    the CPU backend, so it can neither load libtpu nor claim the chip."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")


def _worker_entry(child_sock: socket.socket, node_id: int,
                  slots: int) -> None:
    _pin_off_accelerator()
    _WorkerServer(child_sock, node_id, slots).run()


class _WorkerServer:
    """One storage node: owns its partitions, executes pushed plans with
    an internal ``slots``-thread pool, serves raw projections, and stamps
    a load snapshot on every response."""

    def __init__(self, sock: socket.socket, node_id: int, slots: int):
        self.sock = sock
        self.node = node_id
        self.slots = max(1, slots)
        self.parts: Dict[Tuple[str, int], ColumnTable] = {}
        self.versions: Dict[Tuple[str, int], int] = {}
        self.plans: Dict[str, CompiledPushPlan] = {}
        self.q: "queue.Queue" = queue.Queue()
        self.pending = {"exec": 0, "fetch": 0}
        self.inflight = 0
        self.done = 0
        self.die_after: Optional[int] = None
        self.lock = threading.Lock()
        self.wlock = threading.Lock()
        self.cpu0 = (time.process_time(), time.perf_counter())

    # ------------------------------------------------------------- protocol
    def run(self) -> None:   # pragma: no cover — runs in the child process
        for _ in range(self.slots):
            threading.Thread(target=self._work, daemon=True).start()
        while True:
            try:
                header, body, _ = _read_frame(self.sock)
            except (EOFError, OSError):
                os._exit(0)
            kind = header["kind"]
            if kind == "shutdown":
                os._exit(0)
            elif kind == "load":
                self._install(header, body)
                self._reply({"req": header["req"], "ok": True})
            elif kind == "poll":
                self._reply({"req": header["req"], "ok": True})
            elif kind == "die_after":
                with self.lock:
                    self.die_after = int(header["n"])
                self._reply({"req": header["req"], "ok": True})
            elif kind == "burn":
                for _ in range(int(header.get("tasks", 1))):
                    with self.lock:
                        self.pending["exec"] += 1
                    self.q.put(({"kind": "burn", "req": None,
                                 "seconds": header["seconds"]}, b""))
                self._reply({"req": header["req"], "ok": True})
            else:                       # exec | fetch — the work queue
                if header.get("trace"):
                    # perf_counter is CLOCK_MONOTONIC, the parent's clock
                    header["t_recv"] = time.perf_counter()
                with self.lock:
                    self.pending["exec" if kind == "exec" else "fetch"] += 1
                self.q.put((header, body))

    def _work(self) -> None:   # pragma: no cover — child process threads
        while True:
            header, body = self.q.get()
            if header.get("trace"):
                header["t_start"] = time.perf_counter()
            kind = header["kind"]
            with self.lock:
                if self.die_after is not None and self.done >= self.die_after:
                    # the pinned worker-kill schedule: die mid-wave, with
                    # this request (and any queued peers) in flight
                    os.kill(os.getpid(), signal.SIGKILL)
                self.pending["exec" if kind in ("exec", "burn")
                             else "fetch"] -= 1
                self.inflight += 1
            spans = None
            bufs: List[bytes] = []
            try:
                if kind == "burn":
                    end = time.perf_counter() + float(header["seconds"])
                    x = 1.0
                    while time.perf_counter() < end:
                        x = x * 1.0000001 + 1.0   # real CPU occupancy
                    resp: Dict = {}
                elif kind == "exec":
                    resp, bufs, spans = self._exec(header, body)
                else:
                    resp, bufs, spans = self._fetch(header, body)
                hdr = dict(resp, req=header["req"], ok=True)
            except BaseException as e:  # noqa: BLE001 — shipped to parent
                hdr = {"req": header["req"], "ok": False,
                       "error": f"{type(e).__name__}: {e}"}
                bufs = []
            with self.lock:
                self.inflight -= 1
                self.done += 1
            if spans:
                hdr["spans"] = spans
            if hdr["req"] is not None:
                self._reply(hdr, b"".join(bufs))

    def _reply(self, header: Dict, body: bytes = b"") -> None:
        header["load"] = self._load_snapshot()
        with self.wlock:
            try:
                _write_frame(self.sock, header, body)
            except OSError:   # parent is gone; nothing left to serve
                os._exit(0)

    # ------------------------------------------------------------- handlers
    def _install(self, header: Dict, body) -> None:
        cur = _Cursor(body)
        cols = {c: np.array(_dec_arr(s, cur), copy=True)
                for c, s in header["cols"]}
        key = (header["table"], int(header["index"]))
        self.parts[key] = ColumnTable(cols)
        self.versions[key] = int(header["version"])

    def _compiled(self, header: Dict, cur: _Cursor) -> CompiledPushPlan:
        key = header["plan_key"]
        if "plan" in header:
            spec = bytes(cur.take(header["plan"]))
            if key not in self.plans:
                self.plans[key] = compile_push_plan(decode_plan(spec))
        return self.plans[key]

    def _tabs(self, header: Dict) -> List[ColumnTable]:
        out = []
        for (table, index), ver in zip(header["parts"], header["versions"]):
            key = (table, int(index))
            if self.versions.get(key) != int(ver):
                raise RuntimeError(
                    f"stale partition {key}: worker holds "
                    f"v{self.versions.get(key)}, request wants v{ver}")
            out.append(self.parts[key])
        return out

    def _exec(self, header: Dict, body) -> Tuple[Dict, List[bytes], List]:
        cur = _Cursor(body)
        cplan = self._compiled(header, cur)
        bms = _dec(header["bms"], cur) if "bms" in header else None
        tabs = self._tabs(header)
        if header["executor"] == EXECUTOR_REFERENCE:
            out = [execute_push_plan(cplan.plan, t,
                                     None if bms is None else bms[i])
                   for i, t in enumerate(tabs)]
        else:
            parts_res, aux = cplan.execute_batch_parts(
                tabs, bms, header.get("threshold"))
            out = list(zip(parts_res, aux))
        bufs: List[bytes] = []
        vals = _enc([[res, aux] for res, aux in out], bufs)
        spans = self._spans(header, "worker_execute", tabs, out)
        return {"vals": vals}, bufs, spans

    def _fetch(self, header: Dict, body) -> Tuple[Dict, List[bytes], List]:
        cur = _Cursor(body)
        cplan = self._compiled(header, cur)
        tabs = self._tabs(header)
        projs = [cplan.raw_projection(t) for t in tabs]
        bufs: List[bytes] = []
        vals = _enc(projs, bufs)
        spans = self._spans(header, "worker_fetch", tabs, None)
        return {"vals": vals}, bufs, spans

    def _spans(self, header: Dict, name: str, tabs,
               out) -> Optional[List[Dict]]:
        """The traced request's spans on the shared monotonic clock: the
        wait in this worker's queue (frame read to a slot's pickup) and
        the handling (pickup to the reply's encoded body)."""
        if not header.get("trace"):
            return None
        t_end = time.perf_counter()
        attrs = {"node": self.node, "pid": os.getpid(),
                 "table": header["parts"][0][0], "n_parts": len(tabs)}
        if out is not None:
            attrs["rows_out"] = int(sum(len(res) for res, _ in out))
        parent = header.get("span")
        t_recv, t_start = header["t_recv"], header["t_start"]
        return [{"name": "worker_queue", "t0": t_recv,
                 "dur": t_start - t_recv, "remote_parent": parent,
                 "attrs": {"node": self.node, "pid": attrs["pid"]}},
                {"name": name, "t0": t_start, "dur": t_end - t_start,
                 "remote_parent": parent, "attrs": attrs}]

    def _load_snapshot(self) -> Dict:
        with self.lock:
            snap = {"exec_q": self.pending["exec"],
                    "ship_q": self.pending["fetch"],
                    "inflight": self.inflight, "done": self.done}
        cpu_t, wall_t = time.process_time(), time.perf_counter()
        dcpu = cpu_t - self.cpu0[0]
        dwall = wall_t - self.cpu0[1]
        if dwall > 1e-3:
            self.cpu0 = (cpu_t, wall_t)
            snap["cpu"] = round(min(1.0, dcpu / (dwall * self.slots)), 4)
        else:
            snap["cpu"] = None
        return snap


# ----------------------------------------------------------- parent channel
class WorkerChannel:
    """Parent-side end of one worker's socketpair: a writer lock, a reader
    thread resolving per-request futures, and :class:`WorkerFault`
    mapping for a dead or overdue channel."""

    def __init__(self, node_id: int, slots: int,
                 timeout_s: Optional[float] = None):
        self.node = node_id
        self.timeout_s = timeout_s
        parent_sock, child_sock = socket.socketpair()
        # spawn, not fork: a fork would copy a parent that may already hold
        # the TPU client (its libtpu state and device handles) into every
        # worker; a spawned interpreter inherits only the socket it is given
        ctx = multiprocessing.get_context("spawn")
        self.proc = ctx.Process(target=_worker_entry,
                                args=(child_sock, node_id, slots),
                                daemon=True)
        self.proc.start()
        child_sock.close()
        self.sock = parent_sock
        self._pending: Dict[int, Future] = {}
        self._plock = threading.Lock()
        self._wlock = threading.Lock()
        self._rid = itertools.count()
        self.dead: Optional[str] = None        # fault kind once failed
        self.bytes_sent = 0
        self.bytes_recv = 0
        self.last_load: Optional[Dict] = None
        threading.Thread(target=self._read_loop, daemon=True).start()
        # a spawned interpreter takes a moment to import its way to the
        # serve loop: wait for it once, outside the per-request timeout
        self.request({"kind": "poll"}, timeout=_STARTUP_TIMEOUT_S)

    def _read_loop(self) -> None:
        try:
            while True:
                header, body, n = _read_frame(self.sock)
                self.bytes_recv += n
                self.last_load = header.get("load") or self.last_load
                with self._plock:
                    fut = self._pending.pop(header["req"], None)
                if fut is None:
                    continue
                if header.get("ok"):
                    fut.set_result((header, body))
                else:
                    fut.set_exception(RuntimeError(
                        f"worker {self.node} remote error: "
                        f"{header.get('error')}"))
        except (EOFError, OSError):
            self._fail(_faults.FAULT_CRASH)

    def _fail(self, kind: str) -> None:
        self.dead = kind
        with self._plock:
            pending, self._pending = self._pending, {}
        for fut in pending.values():
            fut.set_exception(_faults.WorkerFault(
                kind, self.node, "channel closed mid-request"))

    def request(self, header: Dict, body: bytes = b"",
                timeout: Optional[float] = None) -> Tuple[Dict, memoryview]:
        if self.dead is not None:
            raise _faults.WorkerFault(self.dead, self.node, "worker dead")
        rid = next(self._rid)
        header["req"] = rid
        fut: Future = Future()
        with self._plock:
            self._pending[rid] = fut
        try:
            with self._wlock:
                self.bytes_sent += _write_frame(self.sock, header, body)
        except OSError as e:
            with self._plock:
                self._pending.pop(rid, None)
            raise _faults.WorkerFault(_faults.FAULT_CRASH, self.node,
                                      f"send failed: {e}")
        try:
            return fut.result(timeout=timeout if timeout is not None
                              else self.timeout_s)
        except FutTimeout:
            with self._plock:
                self._pending.pop(rid, None)
            raise _faults.WorkerFault(_faults.FAULT_TIMEOUT, self.node,
                                      f"request overdue ({self.timeout_s}s)")

    def post(self, header: Dict) -> None:
        """Fire-and-forget (shutdown): no future, failures ignored."""
        header["req"] = None
        try:
            with self._wlock:
                _write_frame(self.sock, header)
        except OSError:
            pass

    def close(self) -> None:
        self.post({"kind": "shutdown"})
        self.proc.join(timeout=2.0)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=2.0)
        try:
            self.sock.close()
        except OSError:
            pass


# --------------------------------------------------------------- the pool
class WorkerPool:
    """One storage-worker process per catalog node.

    Construction spawns the workers and ships each node's partitions over
    the wire (the tier exercises the codec end to end; a spawned worker
    inherits no memory from the parent). ``execute_group``/``fetch_projection``
    are the two tier entry points ``core.runtime`` dispatches through;
    both re-ship any partition whose catalog version moved since the last
    ship (append/update staleness), publish the worker's load snapshot
    into the ``stream.*`` gauges, and surface channel failures as
    :class:`core.faults.WorkerFault` — appending each to the pool's
    real-fault ledger (:attr:`events`) for exact reconciliation."""

    def __init__(self, catalog, pd_slots: int = 2,
                 request_timeout_s: Optional[float] = None):
        self.catalog = catalog
        self.nodes = [n.node_id for n in catalog.nodes]
        self.channels = {n: WorkerChannel(n, pd_slots, request_timeout_s)
                         for n in self.nodes}
        self._shipped_ver: Dict[int, Dict[Tuple[str, int], int]] = \
            {n: {} for n in self.nodes}
        self._shipped_plans: Dict[int, set] = {n: set() for n in self.nodes}
        self._plan_specs: Dict[int, Tuple[str, bytes, object]] = {}
        self._plock = threading.Lock()
        self.events: List[Dict] = []       # real-fault ledger
        self._elock = threading.Lock()
        self.closed = False
        for node in self.nodes:
            for part in catalog.nodes[node].partitions:
                self._ship_partition(node, part)

    # --------------------------------------------------------- partitions
    def _ship_partition(self, node: int, part) -> None:
        data = part.data
        bufs: List[bytes] = []
        cols = [[c, _enc_arr(data.cols[c], bufs)] for c in data.columns]
        self.channels[node].request(
            {"kind": "load", "table": part.table, "index": part.index,
             "version": part.version, "cols": cols}, b"".join(bufs))
        self._shipped_ver[node][(part.table, part.index)] = part.version

    def _refresh_parts(self, node: int, sub) -> None:
        shipped = self._shipped_ver[node]
        for r in sub:
            if shipped.get((r.table, r.part.index)) != r.part.version:
                self._ship_partition(node, r.part)

    # -------------------------------------------------------------- plans
    def _plan_ref(self, node: int, plan) -> Tuple[str, Optional[bytes]]:
        pid = id(plan)
        with self._plock:
            ent = self._plan_specs.get(pid)
            if ent is None:
                spec = encode_plan(plan)
                key = hashlib.blake2b(spec, digest_size=8).hexdigest()
                # the plan ref rides along so id(plan) stays pinned
                ent = self._plan_specs[pid] = (key, spec, plan)
            key, spec, _ = ent
            if key in self._shipped_plans[node]:
                return key, None
            return key, spec

    # ------------------------------------------------------- tier entries
    def execute_group(self, cplan: CompiledPushPlan, sub, executor: str,
                      threshold: Optional[float],
                      bitmaps: Optional[Dict[int, np.ndarray]] = None,
                      parent: Optional[obs_trace.Span] = None
                      ) -> List[Tuple[ColumnTable, Dict]]:
        """Dispatch one pushdown group to its node's worker and decode the
        per-partition ``(result, aux)`` pairs — byte-identical to the
        in-process executor on the same decision vector."""
        node = sub[0].part.node_id
        tr = obs_trace.get_tracer()
        try:
            self._refresh_parts(node, sub)
            key, spec = self._plan_ref(node, cplan.plan)
            header: Dict = {"kind": "exec", "plan_key": key,
                            "executor": executor, "threshold": threshold,
                            "parts": [[r.table, r.part.index] for r in sub],
                            "versions": [r.part.version for r in sub]}
            bufs: List[bytes] = []
            if spec is not None:
                header["plan"] = len(spec)
                bufs.append(spec)
            if bitmaps:
                header["bms"] = _enc([bitmaps[r.req_id] for r in sub], bufs)
            if tr.enabled:
                header["trace"] = True
                header["span"] = parent.sid if parent is not None else None
            rh, rb = self.channels[node].request(header, b"".join(bufs))
            if spec is not None:
                self._shipped_plans[node].add(key)
            out = [(res, aux) for res, aux in _dec(rh["vals"], _Cursor(rb))]
            get_metrics().counter("wire.pushdown_result_bytes").inc(len(rb))
            self._publish(node, rh.get("load"))
            self._adopt(tr, rh.get("spans"), parent)
            return out
        except _faults.WorkerFault as wf:
            self._record_fault(wf, table=sub[0].table, op="exec")
            raise

    def fetch_projection(self, cplan: CompiledPushPlan, sub,
                         parent: Optional[obs_trace.Span] = None
                         ) -> List[ColumnTable]:
        """The pushback transfer, for real: the worker serializes each
        partition's raw accessed-column projection and the decoded bytes
        cross the process boundary — the compute layer replays the
        compiled plan over exactly these tables."""
        node = sub[0].part.node_id
        tr = obs_trace.get_tracer()
        try:
            self._refresh_parts(node, sub)
            key, spec = self._plan_ref(node, cplan.plan)
            header: Dict = {"kind": "fetch", "plan_key": key,
                            "parts": [[r.table, r.part.index] for r in sub],
                            "versions": [r.part.version for r in sub]}
            bufs: List[bytes] = []
            if spec is not None:
                header["plan"] = len(spec)
                bufs.append(spec)
            if tr.enabled:
                header["trace"] = True
                header["span"] = parent.sid if parent is not None else None
            rh, rb = self.channels[node].request(header, b"".join(bufs))
            if spec is not None:
                self._shipped_plans[node].add(key)
            tabs = _dec(rh["vals"], _Cursor(rb))
            get_metrics().counter("wire.pushback_ship_bytes").inc(len(rb))
            self._publish(node, rh.get("load"))
            self._adopt(tr, rh.get("spans"), parent)
            return tabs
        except _faults.WorkerFault as wf:
            self._record_fault(wf, table=sub[0].table, op="fetch")
            raise

    # ----------------------------------------------------------- signals
    def _publish(self, node: int, load: Optional[Dict]) -> None:
        if not load:
            return
        m = get_metrics()
        m.gauge(f"stream.node{node}.exec_queue").set(load["exec_q"])
        m.gauge(f"stream.node{node}.ship_queue").set(load["ship_q"])
        m.gauge(f"storage.node{node}.inflight").set(load["inflight"])
        if load.get("cpu") is not None:
            m.gauge(f"storage.node{node}.cpu").set(load["cpu"])

    def publish_load(self) -> Dict[int, Optional[Dict]]:
        """Poll every live worker and publish its queue-depth / in-flight /
        CPU-occupancy snapshot into the gauges ``MeasuredLoad`` reads
        (``stream.node<N>.exec_queue``/``ship_queue`` plus the
        ``storage.node<N>.*`` extras). Dead workers keep their last
        published value — the breaker, not the gauge, routes around
        them."""
        out: Dict[int, Optional[Dict]] = {}
        for node, ch in self.channels.items():
            try:
                rh, _ = ch.request({"kind": "poll"})
                self._publish(node, rh.get("load"))
                out[node] = rh.get("load")
            except _faults.WorkerFault:
                out[node] = None
        return out

    def _adopt(self, tr, recs, parent) -> None:
        """Stitch worker-side span records into the compute-side trace:
        each record becomes a real span parented under the dispatching
        span, placed at the absolute times the worker read off the
        shared monotonic clock (``time.perf_counter``)."""
        if not recs or not tr.enabled:
            return
        for rec in recs:
            sp = tr.start(rec["name"], cat="worker", parent=parent,
                          **rec.get("attrs", {}))
            if sp is obs_trace.NULL_SPAN:
                continue
            sp.attrs["remote_parent"] = rec.get("remote_parent")
            sp.t0 = float(rec["t0"]) - tr.t0
            tr.end(sp)
            sp.dur = float(rec.get("dur") or 0.0)
            tr.amend(sp)   # re-emit: a streaming sink saw the wrong dur

    def _record_fault(self, wf: "_faults.WorkerFault", table: str,
                      op: str) -> None:
        with self._elock:
            self.events.append({"kind": wf.kind, "node": wf.node,
                                "table": table, "op": op})

    def fault_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        with self._elock:
            for ev in self.events:
                out[ev["kind"]] = out.get(ev["kind"], 0) + 1
        return out

    # ----------------------------------------------------- chaos controls
    def kill(self, node: int) -> None:
        """SIGKILL one worker process (the chaos tests' hammer)."""
        self.channels[node].proc.kill()

    def die_after(self, node: int, n: int) -> None:
        """Pinned worker-kill schedule: the worker SIGKILLs *itself* when
        it is about to start work item ``n+1`` — deterministic by request
        count, guaranteed mid-wave."""
        self.channels[node].request({"kind": "die_after", "n": n})

    def burn(self, node: int, seconds: float, tasks: int = 1) -> None:
        """Occupy ``tasks`` work items of real CPU on one worker — the
        injected storage-side pressure the decision-shift benchmark
        measures the Arbitrator against."""
        self.channels[node].request({"kind": "burn", "seconds": seconds,
                                     "tasks": tasks})

    def wire_bytes(self) -> Dict[str, int]:
        return {"sent": sum(ch.bytes_sent for ch in self.channels.values()),
                "recv": sum(ch.bytes_recv for ch in self.channels.values())}

    def alive(self, node: int) -> bool:
        return self.channels[node].dead is None \
            and self.channels[node].proc.is_alive()

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        for ch in self.channels.values():
            ch.close()


# ------------------------------------------------------------ pool registry
_POOLS: Dict[int, Tuple[object, WorkerPool]] = {}
_POOLS_LOCK = threading.Lock()


def pool_for(catalog, pd_slots: int = 2) -> WorkerPool:
    """The process-wide pool for ``catalog`` (created on first use; the
    registry pins the catalog so ``id()`` keys stay unambiguous). Engine
    configs with ``storage_tier="process"`` and no explicit
    ``worker_pool`` route here."""
    with _POOLS_LOCK:
        ent = _POOLS.get(id(catalog))
        if ent is not None and not ent[1].closed:
            return ent[1]
        pool = WorkerPool(catalog, pd_slots=pd_slots)
        _POOLS[id(catalog)] = (catalog, pool)
        return pool


def close_all_pools() -> None:
    with _POOLS_LOCK:
        pools = [p for _, p in _POOLS.values()]
        _POOLS.clear()
    for p in pools:
        p.close()


atexit.register(close_all_pools)
