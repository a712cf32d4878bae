"""TQP-style residual tensorization: whole residual IR -> fused jax.jit.

The residual interpreter (``compiler/interpreter.py``) walks IR nodes
per-operator in numpy. This module instead *lowers* a query's residual —
Filter / Project / Map / Aggregate / Join / SemiJoin / TopK / Sort /
Shuffle — into **one fused jax program per segment**, jit-compiled once
per input-shape bucket and reusable unchanged on CPU/GPU/TPU (Tensor
Query Processor's design, SNIPPETS.md snippet 1). The lowerings are
chosen for what XLA:CPU is actually good at — gathers, elementwise ops
and reductions — and against what it is bad at (single-threaded sorts,
scatters and ``top_k``), which a measurement pass on this machine showed
to be 3-5x slower than numpy at residual cardinalities:

========== ================================================================
IR node    tensor lowering
========== ================================================================
Filter     predicate closure (``expressions_jax.compile_expr_jnp``) ANDed
           into the validity mask — no gather, rows stay in place
Project    column-subset of the masked table (missing columns drop,
           mirroring the interpreter)
Map        derive lambdas written against numpy trace through a
           numpy-protocol shim (``__array_ufunc__``/``__array_function__``
           routed to jax.numpy), so ``np.maximum``/``np.isin``-style
           derives stay inside the jit instead of host round-trips
Aggregate  keyed: mixed-radix key codes over the *observed* per-key value
           bounds (see below). A domain of up to ``_AGG_DENSE_CAP``
           codes reduces as one dense masked reduction over the rows,
           with no scatter: XLA:TPU serializes a scatter-add's updates
           that collide on one address. Larger domains reduce by
           ``jax.ops.segment_sum``-family scatters. Group compaction is
           a cumsum+searchsorted, with no sort; beyond ``_AGG_DOM_CAP``
           codes, integral keys sort as one packed code and non-integral
           keys lexsort.
           keyless: masked whole-column reductions
Join       build-host / probe-device: every right side is materialized
           host-side as a named build leaf, and a dense key LUT over its
           key domain is scattered in numpy (cheap) -> the in-trace join
           is a pure gather chain (many-to-one; duplicate right keys are
           detected on the host and replay the interpreter oracle).
           When LUT specialization is infeasible (non-integer keys, huge
           domain) the probe uses in-trace sort + ``searchsorted`` +
           gather with an in-program duplicate-key fallback flag
SemiJoin   LUT membership probe on the validity mask (anti negates);
           sorted-membership test when no LUT is available
TopK       ``jax.lax.top_k`` over ±inf-masked scores, static k
Sort       ``jnp.lexsort`` with an invalid-rows-last primary key;
           descending reverses the valid prefix (matches the
           interpreter's ``order[::-1]`` anti-stable tie behavior)
Shuffle    row-preserving no-op (redistribution marker)
PyOp       segmentation boundary: the residual partitions into maximal
           jittable segments around each PyOp, whose host function runs
           on materialized tables between segments
========== ================================================================

Leaf-adjacent {Filter, Project, Map, Shuffle} chains over Merged/Scan
leaves (and over already-materialized PyOp outputs) are *input
preparation*: they are evaluated host-side through the interpreter
(shared-memo per run, so DAG-shared chains evaluate once) before the
tensor program runs, exactly like the storage layer's pushdown stages
run before the residual. That keeps the padded row domain the device
program sees as small as the data actually is, and it is what makes the
join LUTs buildable on the host.

**Observe-first specialization.** The first ``execute`` of a residual
runs the instrumented interpreter oracle (whose result it returns) and
records, per keyed Aggregate, the per-key value bounds of its input, and
per Join/SemiJoin, the right side's key domain — the same measured-not-
assumed discipline as the executor's calibrated gather/concat crossover.
The jitted program bakes those bounds in; an in-trace guard flags any
later run whose keys leave the observed domain, which triggers a
re-observation and a re-specialized jit (bounds are unioned; capped at
``_RESPEC_CAP`` generations before the residual settles on the oracle).

Tables are represented as padded columns plus a validity mask: every
input is padded to a power-of-two row bucket, so repeated runs at
similar cardinalities reuse the compiled program (the jit cache is keyed
by ``(stage, generation, inputs, dtypes, buckets)`` — hit/miss
accounting is returned per run and surfaced in ``QueryRun``). All tensor
arithmetic runs under ``jax.enable_x64(True)`` so results stay
comparable with the float64 numpy oracle (on a TPU, XLA emulates f64 and
i64, so agreement is to ``engine.results_equal``'s tolerance, not
bitwise); the interpreter remains that oracle and ``tests/test_tensorize.py`` pins identity across all 15 TPC-H
residuals, every execution mode, and random decision vectors.

``core.runtime.run_residual`` dispatches between the two backends
(``EngineConfig.residual``); ``"auto"`` uses a calibrated merged-row
crossover (``calibrate_residual_threshold``), overridable via
``REPRO_RESIDUAL_THRESHOLD`` / ``REPRO_NO_CALIBRATE``.
"""
from __future__ import annotations

import dataclasses
import os
import re
import threading
import time
from collections import Counter, OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.compiler import ir
from repro.obs import trace as obs_trace
from repro.obs.metrics import get_metrics
from repro.queryproc import expressions_jax as exj
from repro.queryproc.table import ColumnTable

_MIN_BUCKET = 16
_LUT_CAP = 1 << 25       # max dense key-LUT domain (256 MiB of int64)
_AGG_DOM_CAP = 1 << 18   # max mixed-radix aggregate code domain
_AGG_DENSE_CAP = 4096    # max code domain reduced densely (sweep: PERF.md)
_LEX_CODE_CAP = 1 << 62  # max key domain sorted as one packed int64 code
_RESPEC_CAP = 8          # re-specializations before settling on the oracle


class TensorFallback(Exception):
    """Raised when a lowering guard trips. ``respec=True`` marks guards an
    observation refresh can cure (keys left the observed domain);
    ``respec=False`` marks data shapes the lowering cannot express
    (duplicate right join keys: the tensor join is many-to-one). Either
    way ``execute`` replays the interpreter oracle for this run."""

    def __init__(self, msg: str = "", respec: bool = False):
        super().__init__(msg)
        self.respec = respec


class _MT:
    """Tracing-time masked table: padded columns + validity mask."""
    __slots__ = ("cols", "valid")

    def __init__(self, cols, valid):
        self.cols = cols
        self.valid = valid


def _unshim(v):
    return v.x if isinstance(v, _NpShim) else v


class _NpShim:
    """numpy-protocol adapter around a jax tracer: residual Map derives
    are written against numpy (``np.maximum``, ``np.isin``, operators,
    ``.astype``), and jax tracers in this jax version implement neither
    ``__array_ufunc__`` nor ``__array_function__`` — a raw trace dies
    with a TracerArrayConversionError. Wrapping the derive's inputs here
    reroutes both protocols (and the operator surface) to the
    ``jax.numpy`` twins, so the whole derive stays inside the jit.

    (The obvious alternative — ``jax.pure_callback`` — deadlocks on the
    CPU backend for large programs: the callback runs on an XLA
    execution thread and converting its device-put arguments back to
    numpy blocks on that same busy pool.)"""
    __slots__ = ("x",)
    __array_priority__ = 1000

    def __init__(self, x):
        self.x = x

    # ---- numpy dispatch protocols
    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        import jax.numpy as jnp
        if method != "__call__" or kwargs.get("out") is not None:
            return NotImplemented
        jf = getattr(jnp, ufunc.__name__, None)
        if jf is None:
            return NotImplemented
        kwargs.pop("out", None)
        return _NpShim(jf(*[_unshim(a) for a in inputs], **kwargs))

    def __array_function__(self, func, types, args, kwargs):
        import jax.numpy as jnp
        jf = getattr(jnp, func.__name__, None)
        if jf is None:
            return NotImplemented

        def conv(v):  # jnp rejects raw tuples/lists where numpy coerces
            v = _unshim(v)
            return jnp.asarray(np.asarray(v)) if isinstance(
                v, (tuple, list)) else v

        return _NpShim(jf(*[conv(a) for a in args],
                          **{k: conv(v) for k, v in kwargs.items()}))

    # ---- array-ish surface
    @property
    def dtype(self):
        return self.x.dtype

    @property
    def shape(self):
        return self.x.shape

    @property
    def ndim(self):
        return self.x.ndim

    def astype(self, dt):
        return _NpShim(self.x.astype(dt))

    def __neg__(self):
        return _NpShim(-self.x)

    def __invert__(self):
        return _NpShim(~self.x)


def _shim_binop(name: str, reflected: bool = False):
    import operator
    op = getattr(operator, name)

    def fwd(self, other):
        return _NpShim(op(self.x, _unshim(other)))

    def rev(self, other):
        return _NpShim(op(_unshim(other), self.x))

    return rev if reflected else fwd


for _nm in ("add", "sub", "mul", "truediv", "floordiv", "mod", "pow",
            "and_", "or_", "xor"):
    _dunder = _nm.rstrip("_")
    setattr(_NpShim, f"__{_dunder}__", _shim_binop(_nm))
    setattr(_NpShim, f"__r{_dunder}__", _shim_binop(_nm, reflected=True))
for _nm in ("lt", "le", "gt", "ge", "eq", "ne"):
    setattr(_NpShim, f"__{_nm}__", _shim_binop(_nm))


@dataclasses.dataclass
class _Stage:
    """One maximal jittable segment. ``jit_roots`` are lowered inside a
    single jit; host-resident roots are prepared by the interpreter;
    ``pyop`` (if any) then runs host-side on the materialized root tables
    and its output enters the environment as ``out_name``. ``names`` /
    ``luts`` (the stage's jit inputs) are filled post-observation by
    ``_build_jits``; ``agg_ways`` (how many keyed aggregates its program
    lowers ``dense``, ``scatter`` or ``sort``) by the program's trace."""
    index: int
    roots: Tuple[ir.Node, ...]
    jit_roots: Tuple[ir.Node, ...]
    pyop: Optional[ir.PyOp]
    out_name: Optional[str]
    names: List[str] = dataclasses.field(default_factory=list)
    luts: List[Tuple[str, str, str, bool]] = dataclasses.field(
        default_factory=list)
    agg_ways: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _Artifact:
    """Compile-once product for one residual object. ``obs`` (the
    observation-derived aggregate bounds and join modes) is None until
    the first execute; the jit fns are built from it and rebuilt on each
    re-specialization (``gen`` bumps, the shape cache clears)."""
    stages: List[_Stage]
    pyop_names: Dict[int, str]       # id(PyOp) -> env key
    leaf_names: Dict[int, str]       # id(host-resident node) -> env key
    prep_nodes: Dict[str, ir.Node]   # env key -> host-resident node
    preds: Dict[int, Callable]       # id(Filter) -> jnp predicate closure
    agg_nodes: List[ir.Aggregate]    # keyed aggregates (observation targets)
    jn_nodes: List[ir.Node]          # Join/SemiJoin nodes (mode targets)
    obs: Optional[Dict] = None       # {"agg": {id: spec}, "join": {id: mode}}
    jit_fns: List[Optional[Callable]] = dataclasses.field(
        default_factory=list)
    seen: set = dataclasses.field(default_factory=set)  # jit-cache keys
    gen: int = 0
    respecs: int = 0
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    disabled: bool = False           # respec cap reached: oracle-only
    qid: Optional[str] = None        # names the jitted stages


@dataclasses.dataclass
class TensorRun:
    """One ``execute`` call's result + jit-cache accounting."""
    table: ColumnTable
    jit_hits: int = 0
    jit_misses: int = 0
    fell_back: bool = False
    observed: bool = False
    n_stages: int = 0
    platforms: Tuple[str, ...] = ()   # devices the jitted stages ran on


# ------------------------------------------------------------ compilation
def _postorder_pyops(node: ir.Node) -> List[ir.PyOp]:
    out: List[ir.PyOp] = []
    seen: set = set()

    def rec(n: ir.Node) -> None:
        if id(n) in seen:
            return
        seen.add(id(n))
        for c in n.inputs():
            rec(c)
        if isinstance(n, ir.PyOp):
            out.append(n)

    rec(node)
    return out


def _host_res(n: ir.Node, memo: Dict[int, bool]) -> bool:
    """Host-resident: materializable outside the jit — a leaf table, an
    already-executed PyOp output, or a {Filter,Project,Map,Shuffle}
    chain over one. These become prep units / LUT sources."""
    r = memo.get(id(n))
    if r is None:
        if isinstance(n, (ir.Merged, ir.Scan, ir.PyOp)):
            r = True
        elif isinstance(n, (ir.Filter, ir.Project, ir.Map, ir.Shuffle)):
            r = _host_res(n.child, memo)
        else:
            r = False
        memo[id(n)] = r
    return r


def _assign_leaves(residual: ir.Node, pyops: List[ir.PyOp],
                   pyop_names: Dict[int, str], hmemo: Dict[int, bool]
                   ) -> Tuple[Dict[int, str], Dict[str, ir.Node]]:
    """Name every maximal host-resident subtree the jit segments read:
    bare leaves keep their table name (so the shape-cache key is
    legible), prep chains get ``__prep{n}``, PyOp outputs their stage
    name. Traversal stops at a named subtree except to find embedded
    PyOps, whose children are earlier stages' roots."""
    leaf_names: Dict[int, str] = {}
    prep_nodes: Dict[str, ir.Node] = {}
    seen: set = set()
    ctr = 0

    def name_leaf(n: ir.Node) -> None:
        nonlocal ctr
        if id(n) in leaf_names:
            return
        if isinstance(n, (ir.Merged, ir.Scan)):
            nm = n.table
        elif isinstance(n, ir.PyOp):
            nm = pyop_names[id(n)]
        else:
            nm = f"__prep{ctr}"
            ctr += 1
        leaf_names[id(n)] = nm
        if not isinstance(n, ir.PyOp):
            prep_nodes[nm] = n

    def visit_pyops_under(n: ir.Node) -> None:
        for d in ir.walk(n):
            if isinstance(d, ir.PyOp):
                for c in d.children:
                    visit(c)

    def visit(n: ir.Node) -> None:
        if id(n) in seen:
            return
        seen.add(id(n))
        if _host_res(n, hmemo):
            name_leaf(n)
            visit_pyops_under(n)
            return
        if isinstance(n, (ir.Join, ir.SemiJoin)):
            # build side: always host-materialized (the interpreter builds
            # the small side, the device program probes it with gathers —
            # or the sorted fallback reads it as a padded leaf input)
            visit(n.left)
            name_leaf(n.right)
            visit_pyops_under(n.right)
            return
        for c in n.inputs():
            visit(c)

    visit(residual)
    for p in pyops:
        for c in p.children:
            visit(c)
    return leaf_names, prep_nodes


def compile_residual(residual: ir.Node) -> _Artifact:
    """Partition the residual into maximal jittable segments around its
    PyOps, name the host-resident leaves, and pre-compile the Filter
    predicates. Jit functions are built after the first observation run
    (``_build_jits``) because the aggregate/join lowerings specialize on
    observed key domains."""
    pyops = _postorder_pyops(residual)
    pyop_names = {id(p): f"__pyop{i}" for i, p in enumerate(pyops)}
    hmemo: Dict[int, bool] = {}
    leaf_names, prep_nodes = _assign_leaves(residual, pyops, pyop_names,
                                            hmemo)
    stages: List[_Stage] = []
    for p in pyops:
        roots = tuple(p.children)
        stages.append(_Stage(
            index=len(stages), roots=roots,
            jit_roots=tuple(r for r in roots if not _host_res(r, hmemo)),
            pyop=p, out_name=pyop_names[id(p)]))
    roots = (residual,)
    stages.append(_Stage(
        index=len(stages), roots=roots,
        jit_roots=tuple(r for r in roots if not _host_res(r, hmemo)),
        pyop=None, out_name=None))
    import jax
    with jax.enable_x64(True):
        preds = {id(n): exj.compile_expr_jnp(n.predicate)
                 for n in ir.walk(residual) if isinstance(n, ir.Filter)}
    agg_nodes = [n for n in ir.walk(residual)
                 if isinstance(n, ir.Aggregate) and n.keys]
    jn_nodes = [n for n in ir.walk(residual)
                if isinstance(n, (ir.Join, ir.SemiJoin))]
    return _Artifact(stages=stages, pyop_names=pyop_names,
                     leaf_names=leaf_names, prep_nodes=prep_nodes,
                     preds=preds, agg_nodes=agg_nodes, jn_nodes=jn_nodes)


# ------------------------------------------------------------ observation
def _observe(art: _Artifact, memo: Dict[int, ColumnTable]) -> None:
    """Specialize from an instrumented oracle run: per keyed Aggregate,
    the per-key (min, dim) bounds of its *input* (unioned with prior
    generations, so re-specialization only ever widens); per
    Join/SemiJoin, whether the right side supports a dense host LUT."""
    prev = art.obs or {"agg": {}, "join": {}}
    agg: Dict[int, Tuple] = dict(prev["agg"])
    join: Dict[int, Tuple] = {}
    for node in art.agg_nodes:
        spec = agg.get(id(node))
        if spec == ("lex",):
            continue  # non-integral keys are sticky: stay on the sort path
        ct = memo.get(id(node.child))
        if ct is None:
            if spec is None:
                agg[id(node)] = ("code", (0,) * len(node.keys),
                                 (1,) * len(node.keys))
            continue
        cols = [np.asarray(ct.cols[k]) if k in ct.cols else None
                for k in node.keys]
        if any(c is None or c.dtype.kind not in "iub" for c in cols):
            agg[id(node)] = ("lex",)
            continue
        if len(ct) == 0:
            mins = [0] * len(cols)
            maxs = [0] * len(cols)
        else:
            mins = [int(c.min()) for c in cols]
            maxs = [int(c.max()) for c in cols]
        if spec is not None:
            mins = [min(a, b) for a, b in zip(mins, spec[1])]
            maxs = [max(mx, om + od - 1)
                    for mx, om, od in zip(maxs, spec[1], spec[2])]
        dims = [mx - mn + 1 for mn, mx in zip(mins, maxs)]
        dom = 1
        for d in dims:
            dom *= d
        if dom <= _AGG_DOM_CAP:
            agg[id(node)] = ("code", tuple(mins), tuple(dims))
        elif dom < _LEX_CODE_CAP:
            agg[id(node)] = ("lex", tuple(mins), tuple(dims))
        else:
            agg[id(node)] = ("lex",)
    for j, node in enumerate(art.jn_nodes):
        mode: Tuple = ("sorted",)
        rname = art.leaf_names.get(id(node.right))
        rt = memo.get(id(node.right))
        if rname is not None and rt is not None and node.rkey in rt.cols:
            rk = np.asarray(rt.cols[node.rkey])
            if rk.dtype.kind in "iub":
                dom = (1 if len(rk) == 0
                       else int(rk.max()) - int(rk.min()) + 1)
                if dom <= _LUT_CAP:
                    mode = ("lut", f"__lut{j}", rname)
        join[id(node)] = mode
    art.obs = {"agg": agg, "join": join}


def _stage_io(art: _Artifact, st: _Stage
              ) -> Tuple[List[str], List[Tuple[str, str, str, bool]]]:
    """Jit inputs for one stage: the host-resident leaf names its lowering
    will read, plus the LUT specs (name, right leaf, right key, is_join)
    to build on the host each run. Mirrors ``_lower_node``'s recursion —
    LUT semi-joins never read the right table, LUT joins read it only
    for the gathers."""
    names: List[str] = []
    luts: List[Tuple[str, str, str, bool]] = []
    seen: set = set()

    def add(nm: str) -> None:
        if nm not in names:
            names.append(nm)

    def rec(n: ir.Node) -> None:
        if id(n) in seen:
            return
        seen.add(id(n))
        nm = art.leaf_names.get(id(n))
        if nm is not None:
            add(nm)
            return
        if isinstance(n, (ir.Join, ir.SemiJoin)):
            mode = art.obs["join"][id(n)]
            if mode[0] == "lut":
                rec(n.left)
                _, jname, rname = mode
                luts.append((jname, rname, n.rkey, isinstance(n, ir.Join)))
                if isinstance(n, ir.Join):
                    add(rname)
                return
        for c in n.inputs():
            rec(c)

    for r in st.jit_roots:
        rec(r)
    return names, luts


def _build_jits(art: _Artifact) -> None:
    import jax
    from repro import jaxcache
    jaxcache.enable_compile_cache()
    fns: List[Optional[Callable]] = []
    for st in art.stages:
        st.names, st.luts = _stage_io(art, st)
        fns.append(jax.jit(_make_stage_fn(st, art)) if st.jit_roots
                   else None)
    art.jit_fns = fns
    art.seen = set()


def stage_name(qid: Optional[str], index: int) -> str:
    """The jitted stage's function name, so that its XLA module and its
    profiler events read ``jit_residual_Q18_s0``."""
    q = re.sub(r"\W", "_", qid) if qid else ""
    return f"residual_{q}_s{index}" if q else f"residual_s{index}"


def _make_stage_fn(stage: _Stage, art: _Artifact) -> Callable:
    def stage_fn(inputs):
        import jax.numpy as jnp
        ctx: Dict = {"memo": {}, "flags": [], "respec": [],
                     "inputs": inputs, "art": art, "agg_ways": []}
        outs = []
        for root in stage.jit_roots:
            mt = _lower(root, ctx)
            outs.append({"cols": dict(mt.cols), "valid": mt.valid})
        stage.agg_ways = dict(Counter(ctx["agg_ways"]))
        flag = jnp.asarray(False)
        for f in ctx["flags"]:
            flag = flag | f
        resp = jnp.asarray(False)
        for f in ctx["respec"]:
            resp = resp | f
        return {"outs": outs, "fallback": flag, "respec": resp}

    stage_fn.__name__ = stage_fn.__qualname__ = stage_name(art.qid,
                                                           stage.index)
    return stage_fn


# --------------------------------------------------------------- lowering
def _lower(node: ir.Node, ctx: Dict) -> _MT:
    memo = ctx["memo"]
    if id(node) in memo:
        return memo[id(node)]
    out = _lower_node(node, ctx)
    memo[id(node)] = out
    return out


def _leaf(name: str, ctx: Dict) -> _MT:
    leaf = ctx["inputs"][name]
    return _MT(dict(leaf["cols"]), leaf["valid"])


def _lower_node(node: ir.Node, ctx: Dict) -> _MT:
    import jax.numpy as jnp

    nm = ctx["art"].leaf_names.get(id(node))
    if nm is not None:  # host-resident: prep chain / leaf / PyOp output
        return _leaf(nm, ctx)
    if isinstance(node, ir.Shuffle):  # redistribution marker: row-preserving
        return _lower(node.child, ctx)

    if isinstance(node, ir.Filter):
        t = _lower(node.child, ctx)
        mask = ctx["art"].preds[id(node)](t.cols)
        return _MT(t.cols, t.valid & mask)

    if isinstance(node, ir.Project):
        t = _lower(node.child, ctx)
        return _MT({c: t.cols[c] for c in node.columns if c in t.cols},
                   t.valid)

    if isinstance(node, ir.Map):
        t = _lower(node.child, ctx)
        cols = dict(t.cols)
        for name, incols, fn in node.derives:
            args = [_NpShim(cols[c]) for c in incols]
            cols[name] = jnp.asarray(_unshim(fn(*args)))
        return _MT(cols, t.valid)

    if isinstance(node, ir.Aggregate):
        return _lower_aggregate(node, _lower(node.child, ctx), ctx)
    if isinstance(node, ir.Join):
        return _lower_join(node, ctx)
    if isinstance(node, ir.SemiJoin):
        return _lower_semijoin(node, ctx)
    if isinstance(node, ir.TopK):
        return _lower_topk(node, _lower(node.child, ctx))
    if isinstance(node, ir.Sort):
        return _lower_sort(node, _lower(node.child, ctx))
    raise TypeError(f"unknown IR node: {node!r}")


def _minmax_sentinel(dtype, want_max: bool):
    import jax.numpy as jnp
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if want_max else -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max if want_max else info.min


def _lower_aggregate(node: ir.Aggregate, t: _MT, ctx: Dict) -> _MT:
    if not node.keys:
        return _agg_keyless(node, t)
    spec = ctx["art"].obs["agg"][id(node)]
    if spec[0] == "code":
        return _agg_code(node, t, spec, ctx)
    ctx["agg_ways"].append("sort")
    return _agg_lex(node, t, spec, ctx)


def _agg_keyless(node: ir.Aggregate, t: _MT) -> _MT:
    import jax.numpy as jnp

    # keyless: one output row; the all-invalid (empty-input) case
    # selects 0, matching the interpreter's empty-table row
    n_valid = jnp.sum(t.valid)
    out = {}
    for name, fn, col in node.aggs:
        arr = t.cols[col] if col else next(iter(t.cols.values()))
        if fn == "count":
            v = n_valid.astype(jnp.int64)
        elif fn == "sum":
            v = jnp.sum(jnp.where(t.valid, arr, jnp.zeros((), arr.dtype)))
        elif fn == "mean":
            s = jnp.sum(jnp.where(t.valid, arr, 0).astype(jnp.float64))
            v = jnp.where(n_valid > 0, s / jnp.maximum(n_valid, 1), 0.0)
        else:
            sent = _minmax_sentinel(arr.dtype, want_max=(fn == "min"))
            red = jnp.min if fn == "min" else jnp.max
            v = red(jnp.where(t.valid, arr, sent))
            v = jnp.where(n_valid > 0, v, jnp.zeros((), v.dtype))
        out[name] = v[None]
    return _MT(out, jnp.ones((1,), bool))


def _radix_code(node: ir.Aggregate, t: _MT, mins, dims, ctx: Dict):
    """Each row's group keys as one mixed-radix int64 code over the
    observed per-key bounds (ascending code order == ascending
    lexicographic key order). Rows whose keys left the bounds raise the
    in-trace respec flag. Returns (domain size, strides, codes)."""
    import jax.numpy as jnp

    D = 1
    for d in dims:
        D *= d
    strides = []
    s = 1
    for d in reversed(dims):
        strides.append(s)
        s *= d
    strides = list(reversed(strides))
    oob = jnp.zeros(t.valid.shape, bool)
    code = jnp.zeros(t.valid.shape, jnp.int64)
    for k, mn, d, stp in zip(node.keys, mins, dims, strides):
        off = t.cols[k].astype(jnp.int64) - mn
        oob = oob | (off < 0) | (off >= d)
        code = code + jnp.clip(off, 0, d - 1) * stp
    ctx["respec"].append(jnp.any(t.valid & oob))
    return D, strides, code


def _agg_code(node: ir.Aggregate, t: _MT, spec: Tuple, ctx: Dict) -> _MT:
    """Sort-free grouped aggregation: each row's keys encode into one
    mixed-radix code over the observed per-key bounds, the group
    reductions run directly on the codes (ascending code order == the
    ascending lexicographic key order np.unique gives the interpreter),
    and group compaction is a cumsum + searchsorted over the code domain.
    Rows whose keys left the observed domain raise the in-trace respec
    flag.

    A domain of at most ``_AGG_DENSE_CAP`` codes reduces densely
    (``_dense_group_reduce``): a scatter-add serializes updates that
    collide on one address on XLA:TPU, and in a small domain nearly all
    of them do. Larger domains scatter (``segment_sum`` family), with
    invalid rows parked in the extra segment ``D``."""
    import jax
    import jax.numpy as jnp

    _, mins, dims = spec
    D, strides, code = _radix_code(node, t, mins, dims, ctx)
    key_dtypes = [t.cols[k].dtype for k in node.keys]

    if D <= _AGG_DENSE_CAP:
        ctx["agg_ways"].append("dense")
        wanted = {("sum" if fn == "mean" else fn, col): t.cols[col]
                  for _, fn, col in node.aggs if fn != "count"}
        cnt, present, dense = _dense_group_reduce(code, t.valid, D, wanted)

        def group(kind, col):
            return dense[(kind, col)]
    else:
        ctx["agg_ways"].append("scatter")
        gid = jnp.where(t.valid, code, D)
        cnt = jax.ops.segment_sum(t.valid.astype(jnp.int64), gid,
                                  num_segments=D + 1)[:D]
        present = cnt > 0

        def group(kind, col):
            vals = t.cols[col]
            if kind == "sum":
                masked = jnp.where(t.valid, vals, 0).astype(jnp.float64)
                return jax.ops.segment_sum(masked, gid,
                                           num_segments=D + 1)[:D]
            sent = _minmax_sentinel(vals.dtype, want_max=(kind == "min"))
            red = (jax.ops.segment_min if kind == "min"
                   else jax.ops.segment_max)
            return red(jnp.where(t.valid, vals, sent), gid,
                       num_segments=D + 1)[:D]
    n_groups = jnp.sum(present)
    ranks = jnp.cumsum(present.astype(jnp.int64))
    oc = jnp.clip(jnp.searchsorted(ranks, jnp.arange(1, D + 1)), 0, D - 1)
    out = {}
    for k, mn, d, stp, dt in zip(node.keys, mins, dims, strides, key_dtypes):
        out[k] = (mn + (oc // stp) % d).astype(dt)
    for name, fn, col in node.aggs:
        if fn == "count":
            out[name] = cnt[oc]
        elif fn == "mean":
            out[name] = (group("sum", col) / jnp.maximum(cnt, 1))[oc]
        else:
            out[name] = group(fn, col)[oc]
    return _MT(out, jnp.arange(D) < n_groups)


def _dense_group_reduce(code, valid, D: int, wanted: Dict):
    """Per code: its valid rows' count, whether it has any, and per
    ``(kind, column)`` of ``wanted`` (kind ``sum`` in float64, ``min`` or
    ``max``) the reduction of that column over them. One variadic reduce
    over the (D, N) mask ``valid & (code == d)`` computes all of them; the
    mask is broadcast, never stored, because XLA fuses it into the reduce,
    on XLA:TPU and on XLA:CPU alike. (A lone count would not do: XLA:CPU
    rewrites a single-operand reduce into a tree of partial reductions
    over a stored N x D input, hence the ``any`` beside it.) The count
    sums int32 (exact: N < 2^31) and widens to int64."""
    import jax
    import jax.numpy as jnp

    hit = ((code.astype(jnp.int32)[None, :]
            == jnp.arange(D, dtype=jnp.int32)[:, None]) & valid[None, :])
    kinds = ["sum", "any"]
    operands = [hit.astype(jnp.int32), hit]
    inits = [np.int32(0), np.bool_(False)]
    for (kind, _), vals in wanted.items():
        if kind == "sum":
            vals = vals.astype(jnp.float64)
            ident = np.float64(0)
        else:
            ident = np.asarray(
                _minmax_sentinel(vals.dtype, want_max=(kind == "min")),
                vals.dtype)
        kinds.append(kind)
        operands.append(jnp.where(hit, vals[None, :], ident))
        inits.append(ident)
    combine = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum,
               "any": jnp.logical_or}
    reduced = jax.lax.reduce(
        tuple(operands), tuple(inits),
        lambda a, b: tuple(combine[k](x, y) for k, x, y in zip(kinds, a, b)),
        (1,))
    return (reduced[0].astype(jnp.int64), reduced[1],
            dict(zip(wanted, reduced[2:])))


def _agg_lex(node: ir.Aggregate, t: _MT, spec: Tuple, ctx: Dict) -> _MT:
    """Grouped aggregation for huge-domain or non-integral keys: sorted
    keys -> group-boundary flags -> segment reductions. Integral keys
    (``spec`` carries their observed bounds) sort as one mixed-radix
    code, so the sort has one key whatever the number of group keys:
    XLA:TPU's compile time for a sort grows steeply with its key count.
    Other keys lexsort. Slower than ``_agg_code`` (sorts), but only the
    integral case assumes anything about the key values."""
    import jax
    import jax.numpy as jnp

    n = t.valid.shape[0]
    key_arrs = [t.cols[k] for k in node.keys]
    # invalid rows sort last; groups are contiguous runs of equal keys
    # among the valid prefix (lexicographic ascending — the exact group
    # order np.unique gives the interpreter)
    if len(spec) == 3:
        D, _, code = _radix_code(node, t, spec[1], spec[2], ctx)
        code = jnp.where(t.valid, code, D)
        code, order = jax.lax.sort((code, jax.lax.iota(jnp.int32, n)),
                                   num_keys=1, is_stable=True)
        runs = [code]
    else:
        inval = (~t.valid).astype(jnp.int32)
        order = jnp.lexsort(tuple(reversed(key_arrs)) + (inval,))
        runs = [a[order] for a in key_arrs]
    vs = t.valid[order]
    ks = [a[order] for a in key_arrs]
    if n > 1:
        same = jnp.ones((n - 1,), bool)
        for a in runs:
            same = same & (a[1:] == a[:-1])
        changed = jnp.concatenate([jnp.ones((1,), bool), ~same])
    else:
        changed = jnp.ones((n,), bool)
    new_group = vs & changed
    n_groups = jnp.sum(new_group)
    # invalid rows park in segment n-1: they exist only when n_groups < n,
    # so the segment they pollute is always masked-out padding
    gid = jnp.where(vs, jnp.cumsum(new_group) - 1, n - 1)
    starts = jnp.clip(
        jax.ops.segment_min(jnp.arange(n), gid, num_segments=n), 0, n - 1)
    out = {k: a[starts] for k, a in zip(node.keys, ks)}
    for name, fn, col in node.aggs:
        if fn == "count":
            out[name] = jax.ops.segment_sum(vs.astype(jnp.int64), gid,
                                            num_segments=n)
            continue
        vals = t.cols[col][order]
        if fn == "sum":
            out[name] = jax.ops.segment_sum(
                jnp.where(vs, vals, 0).astype(jnp.float64), gid,
                num_segments=n)
        elif fn == "mean":
            sm = jax.ops.segment_sum(
                jnp.where(vs, vals, 0).astype(jnp.float64), gid,
                num_segments=n)
            c = jax.ops.segment_sum(vs.astype(jnp.int64), gid,
                                    num_segments=n)
            out[name] = sm / jnp.maximum(c, 1)
        elif fn == "min":
            out[name] = jax.ops.segment_min(vals, gid, num_segments=n)
        else:
            out[name] = jax.ops.segment_max(vals, gid, num_segments=n)
    return _MT(out, jnp.arange(n) < n_groups)


def _lut_probe(l: _MT, lkey: str, jname: str, ctx: Dict):
    """Probe a host-built dense key LUT: two gathers and a few compares —
    the whole join, as far as the device program is concerned."""
    import jax.numpy as jnp

    li = ctx["inputs"][jname]
    lut, kmin = li["lut"], li["kmin"]
    size = lut.shape[0]
    off = l.cols[lkey].astype(jnp.int64) - kmin
    inb = (off >= 0) & (off < size)
    ridx = lut[jnp.clip(off, 0, size - 1)]
    return l.valid & inb & (ridx >= 0), ridx


def _sorted_lookup(l: _MT, r: _MT, lkey: str, rkey: str):
    """General join/semi-join probe for non-LUT rights: sort the valid
    right keys (invalid -> +inf keeps the array fully sorted),
    searchsorted the left keys."""
    import jax.numpy as jnp

    n = r.valid.shape[0]
    rk = jnp.where(r.valid, r.cols[rkey].astype(jnp.float64), jnp.inf)
    order = jnp.argsort(rk)
    rs = rk[order]
    lk = l.cols[lkey].astype(jnp.float64)
    lo = jnp.clip(jnp.searchsorted(rs, lk), 0, n - 1)
    found = l.valid & (rs[lo] == lk)
    return order, rs, lo, found


def _lower_join(node: ir.Join, ctx: Dict) -> _MT:
    import jax.numpy as jnp

    l = _lower(node.left, ctx)
    mode = ctx["art"].obs["join"][id(node)]
    if mode[0] == "lut":
        _, jname, rname = mode
        found, ridx = _lut_probe(l, node.lkey, jname, ctx)
        r = ctx["inputs"][rname]
        safe = jnp.clip(ridx, 0, None)
        cols = dict(l.cols)
        for k, v in r["cols"].items():
            if k != node.rkey or node.lkey != node.rkey:
                cols[k if k not in cols else f"r_{k}"] = v[safe]
        return _MT(cols, found)
    r = _lower(node.right, ctx)
    order, rs, lo, found = _sorted_lookup(l, r, node.lkey, node.rkey)
    ridx = order[lo]
    cols = dict(l.cols)
    for k, v in r.cols.items():
        if k != node.rkey or node.lkey != node.rkey:
            cols[k if k not in cols else f"r_{k}"] = v[ridx]
    # m:1 guard: adjacent equal *valid* (finite) sorted keys mean a left
    # row could match several right rows — the host replays the oracle
    if rs.shape[0] > 1:
        ctx["flags"].append(
            jnp.any((rs[1:] == rs[:-1]) & jnp.isfinite(rs[:-1])))
    return _MT(cols, found)


def _lower_semijoin(node: ir.SemiJoin, ctx: Dict) -> _MT:
    l = _lower(node.left, ctx)
    mode = ctx["art"].obs["join"][id(node)]
    if mode[0] == "lut":
        found, _ = _lut_probe(l, node.lkey, mode[1], ctx)
    else:
        r = _lower(node.right, ctx)
        _, _, _, found = _sorted_lookup(l, r, node.lkey, node.rkey)
    mask = l.valid & ~found if node.anti else found
    return _MT(l.cols, mask)


def _lower_topk(node: ir.TopK, t: _MT) -> _MT:
    import jax
    import jax.numpy as jnp

    n = t.valid.shape[0]
    k = min(node.k, n)
    v = t.cols[node.col].astype(jnp.float64)
    scores = jnp.where(t.valid, -v if node.ascending else v, -jnp.inf)
    _, idx = jax.lax.top_k(scores, k)
    return _MT({c: a[idx] for c, a in t.cols.items()},
               jnp.arange(k) < jnp.minimum(k, jnp.sum(t.valid)))


def _lower_sort(node: ir.Sort, t: _MT) -> _MT:
    import jax.numpy as jnp

    n = t.valid.shape[0]
    inval = (~t.valid).astype(jnp.int32)
    order = jnp.lexsort(
        tuple(t.cols[c] for c in reversed(node.columns)) + (inval,))
    n_valid = jnp.sum(t.valid)
    if not node.ascending:
        # reverse only the valid prefix: identical tie order to the
        # interpreter's full-array order[::-1] on its (all-valid) rows
        i = jnp.arange(n)
        order = order[jnp.where(i < n_valid, n_valid - 1 - i, i)]
    return _MT({c: a[order] for c, a in t.cols.items()},
               jnp.arange(n) < n_valid)


# --------------------------------------------------------- host LUT build
def _build_lut(rt: ColumnTable, rkey: str, is_join: bool
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Dense key -> right-row-index LUT over the right side's key domain
    (-1 = absent), built with numpy's (fast, parallel-enough) scatter.
    The length is pow2-bucketed so re-runs at similar domains reuse the
    jit; ``kmin`` rides along as a dynamic scalar input."""
    rk = np.asarray(rt.cols[rkey])
    if rk.dtype.kind not in "iub":
        raise TensorFallback("non-integral LUT join key")
    n = len(rk)
    if n == 0:
        return np.full(_MIN_BUCKET, -1, np.int64), np.asarray(0, np.int64)
    kmin = int(rk.min())
    dom = int(rk.max()) - kmin + 1
    if dom > _LUT_CAP:
        raise TensorFallback("LUT key domain left the observed cap",
                             respec=True)
    lut = np.full(_bucket(dom), -1, np.int64)
    offs = rk.astype(np.int64) - kmin
    lut[offs] = np.arange(n, dtype=np.int64)
    if is_join and int((lut >= 0).sum()) != n:
        raise TensorFallback("duplicate right join keys (m:n)")
    return lut, np.asarray(kmin, np.int64)


# ------------------------------------------------------- artifact caching
_ART_CACHE: "OrderedDict[int, Tuple[ir.Node, _Artifact]]" = OrderedDict()
_ART_CACHE_CAP = 128
# run_stream's compute pool calls execute from several threads
_ART_LOCK = threading.Lock()


def _artifact(residual: ir.Node) -> _Artifact:
    """Compile-once LRU keyed by residual identity (the node is retained,
    so its id cannot be reused while cached) — same discipline as
    ``executor.compile_push_plan`` and the interpreter's ``_PRED_CACHE``."""
    with _ART_LOCK:
        hit = _ART_CACHE.get(id(residual))
        if hit is not None and hit[0] is residual:
            _ART_CACHE.move_to_end(id(residual))
            return hit[1]
        return _compile_artifact(residual)


def _compile_artifact(residual: ir.Node) -> _Artifact:
    tr = obs_trace.get_tracer()
    with tr.span("residual_compile", cat="compiler",
                 shape=ir.describe(residual)) as sp:
        t0 = time.perf_counter()
        art = compile_residual(residual)
        get_metrics().counter("residual.compiles").inc()
        if tr.enabled:
            sp.set(n_stages=len(art.stages),
                   compile_ms=round(1e3 * (time.perf_counter() - t0), 3))
    _ART_CACHE[id(residual)] = (residual, art)
    while len(_ART_CACHE) > _ART_CACHE_CAP:
        _ART_CACHE.popitem(last=False)
    return art


# -------------------------------------------------------------- execution
def _bucket(rows: int) -> int:
    b = _MIN_BUCKET
    while b < rows:
        b <<= 1
    return b


def _pad_table(tab: ColumnTable) -> Tuple[Dict, Tuple]:
    rows = len(tab)
    b = _bucket(rows)
    valid = np.zeros(b, bool)
    valid[:rows] = True
    cols = {}
    for c, a in tab.cols.items():
        if b == rows:
            cols[c] = a
        else:
            pad = np.zeros(b - rows, a.dtype)
            cols[c] = np.concatenate([a, pad])
    sig = (b,) + tuple(sorted((c, a.dtype.str) for c, a in tab.cols.items()))
    return {"cols": cols, "valid": valid}, sig


def _unpad(out: Dict) -> ColumnTable:
    mask = np.asarray(out["valid"])
    return ColumnTable({c: np.asarray(a)[mask]
                        for c, a in out["cols"].items()})


def _observe_run(art: _Artifact, residual: ir.Node,
                 merged: Dict[str, ColumnTable],
                 qid: Optional[str]) -> TensorRun:
    """First execute of a residual: run the instrumented oracle, record
    aggregate key bounds / join LUT feasibility from its memo, and build
    the specialized jit fns. The oracle's table is this run's result."""
    from repro.compiler import interpreter

    tr = obs_trace.get_tracer()
    with tr.span("residual_observe", cat="compiler") as sp:
        t0 = time.perf_counter()
        memo: Dict[int, ColumnTable] = {}
        result = interpreter._run(residual, merged, memo)
        _observe(art, memo)
        art.qid = qid
        _build_jits(art)
        if tr.enabled:
            sp.set(n_stages=len(art.stages),
                   ms=round(1e3 * (time.perf_counter() - t0), 3))
    m = get_metrics()
    m.counter("residual.observes").inc()
    m.counter("residual.tensor.runs").inc()
    return TensorRun(table=result, observed=True, n_stages=len(art.stages))


def _respecialize(art: _Artifact, residual: ir.Node,
                  merged: Dict[str, ColumnTable]) -> ColumnTable:
    """An in-trace domain guard tripped: re-observe on the offending
    input (bounds union, so specialization only widens), rebuild the jit
    fns, bump the generation. Capped: a residual whose key domains never
    settle goes back to the oracle for good."""
    from repro.compiler import interpreter

    with art.lock:
        art.respecs += 1
        if art.respecs > _RESPEC_CAP:
            art.disabled = True
            return interpreter.run(residual, merged)
        memo: Dict[int, ColumnTable] = {}
        result = interpreter._run(residual, merged, memo)
        _observe(art, memo)
        _build_jits(art)
        art.gen += 1
        get_metrics().counter("residual.respecs").inc()
        return result


def execute(residual: ir.Node, merged: Dict[str, ColumnTable],
            qid: Optional[str] = None) -> TensorRun:
    """Run a residual through the tensor backend. Results are identical to
    ``interpreter.run`` (the oracle); on a lowering-guard trip
    (``TensorFallback``) the oracle is replayed host-side and ``fell_back``
    is set. Any other error — a lowering, compile or device failure —
    counts in ``residual.errors`` and raises: it never turns into a
    silent interpreter run. ``qid`` names the jitted stages the first
    call builds (``stage_name``).

    Each jitted stage call runs in four steps, each a child span of the
    caller's (attrs ``qid``, ``stage``, ``hit``): ``residual_prep``
    (host prep chains, bucket padding, join LUTs), ``residual_h2d`` (one
    ``jax.device_put`` of the inputs; traced, it waits for the copy),
    ``residual_device`` (the jit call until its guard flags are on the
    host: device work plus any wait behind other callers) and
    ``residual_d2h`` (readback). A ``PyOp`` stage runs its host function,
    with the host tables it reads, in a fifth step, ``residual_pyop``
    (attrs ``qid``, ``stage``). A guard trip's oracle replay, and the
    re-observation and rebuild of ``_respecialize``, run in one
    ``residual_fallback`` span (attrs ``qid``, ``reason``, ``respec``);
    so does every later call's replay once the respecialization cap has
    disabled the artifact (``reason`` "disabled").
    ``residual.h2d_bytes`` counts the bytes put on the device;
    ``residual.agg.dense``, ``.scatter`` and ``.sort`` count the call's
    keyed aggregates by their lowering."""
    import jax
    from repro.compiler import interpreter

    art = _artifact(residual)
    tr = obs_trace.get_tracer()
    m = get_metrics()
    if art.disabled:
        m.counter("residual.fallbacks").inc()
        with tr.span("residual_fallback", "compiler", qid=qid,
                     reason="disabled", respec=False):
            table = interpreter.run(residual, merged)
        return TensorRun(table=table, fell_back=True,
                         n_stages=len(art.stages))
    if art.obs is None:
        with art.lock:
            if art.obs is None:
                return _observe_run(art, residual, merged, qid)

    hits = misses = 0
    env: Dict[str, ColumnTable] = {}        # PyOp stage outputs
    imemo: Dict[int, ColumnTable] = {}      # shared host-prep memo
    host_tabs: Dict[str, ColumnTable] = {}
    result: Optional[ColumnTable] = None
    fell_back = False
    platforms: set = set()
    h2d = m.counter("residual.h2d_bytes")

    def host_tab(name: str) -> ColumnTable:
        t = env.get(name)
        if t is not None:
            return t
        t = host_tabs.get(name)
        if t is None:
            t = interpreter._run(art.prep_nodes[name], merged, imemo)
            host_tabs[name] = t
        return t

    try:
        with jax.enable_x64(True):
            for st in art.stages:
                out_tabs: Dict[int, ColumnTable] = {}
                if st.jit_roots:
                    attrs = {"qid": qid, "stage": st.index}
                    with tr.span("residual_prep", "compiler", **attrs) as sp:
                        inputs: Dict = {}
                        key: Tuple = (st.index, art.gen)
                        for name in st.names:
                            inputs[name], sig = _pad_table(host_tab(name))
                            key += (name,) + sig
                        for jname, rname, rkey, is_join in st.luts:
                            lut, kmin = _build_lut(host_tab(rname), rkey,
                                                   is_join)
                            inputs[jname] = {"lut": lut, "kmin": kmin}
                            key += (jname, lut.shape[0])
                        attrs["hit"] = key in art.seen
                        sp.set(hit=attrs["hit"])
                    if attrs["hit"]:
                        hits += 1
                    else:
                        misses += 1
                        art.seen.add(key)
                    with tr.span("residual_h2d", "compiler", **attrs):
                        h2d.inc(sum(a.nbytes for a in
                                    jax.tree_util.tree_leaves(inputs)))
                        dev_inputs = jax.device_put(inputs)
                        del inputs       # the padded host copies go here
                        if tr.enabled:
                            jax.block_until_ready(dev_inputs)
                    with tr.span("residual_device", "compiler", **attrs):
                        out = art.jit_fns[st.index](dev_inputs)
                        respec = bool(out["respec"])
                        fallback = bool(out["fallback"])
                    for way, n in st.agg_ways.items():
                        m.counter(f"residual.agg.{way}").inc(n)
                    if respec:
                        raise TensorFallback(
                            "aggregate keys left the observed domain",
                            respec=True)
                    if fallback:
                        raise TensorFallback(f"stage {st.index}")
                    with tr.span("residual_d2h", "compiler", **attrs):
                        platforms.update(d.platform for a in
                                         jax.tree_util.tree_leaves(out)
                                         for d in a.devices())
                        for root, o in zip(st.jit_roots, out["outs"]):
                            out_tabs[id(root)] = _unpad(o)
                        del out, dev_inputs      # frees the device buffers
                if st.pyop is not None:
                    with tr.span("residual_pyop", "compiler", qid=qid,
                                 stage=st.index):
                        tables = [out_tabs[id(r)] if id(r) in out_tabs
                                  else host_tab(art.leaf_names[id(r)])
                                  for r in st.roots]
                        t = st.pyop.fn(*tables)
                    env[st.out_name] = t
                    imemo[id(st.pyop)] = t
                else:
                    r0 = st.roots[0]
                    result = (out_tabs[id(r0)] if id(r0) in out_tabs
                              else host_tab(art.leaf_names[id(r0)]))
    except TensorFallback as e:
        fell_back = True
        m.counter("residual.fallbacks").inc()
        with tr.span("residual_fallback", "compiler", qid=qid,
                     reason=str(e), respec=e.respec):
            if e.respec:
                result = _respecialize(art, residual, merged)
            if result is None:
                result = interpreter.run(residual, merged)
    except Exception:
        m.counter("residual.errors").inc()
        raise
    m.counter("residual.tensor.runs").inc()
    m.counter("residual.jit_cache.hits").inc(hits)
    m.counter("residual.jit_cache.misses").inc(misses)
    assert result is not None
    return TensorRun(table=result, jit_hits=hits, jit_misses=misses,
                     fell_back=fell_back, n_stages=len(art.stages),
                     platforms=tuple(sorted(platforms)))


def run(residual: ir.Node, merged: Dict[str, ColumnTable]) -> ColumnTable:
    """Interpreter-signature twin: evaluate and return just the table."""
    return execute(residual, merged).table


# ------------------------------------------------- auto-dispatch crossover
DEFAULT_RESIDUAL_THRESHOLD = 64_000  # merged rows; used when not calibrated
_AUTO_THRESHOLD: Optional[float] = None


def calibrate_residual_threshold(
        sizes: Tuple[int, ...] = (4_000, 16_000, 64_000),
        repeats: int = 3) -> float:
    """Measure the interpreter-vs-tensor crossover on a synthetic
    join+aggregate residual (the residual-dominant shape) and return the
    merged-row count above which the warm tensor backend wins on this
    machine. Scans sizes downward and stops at the first interpreter win,
    so a noisy tensor win at tiny sizes can never drag the threshold down
    below a size where the interpreter is actually faster."""
    from repro.compiler import interpreter

    rng = np.random.default_rng(0)
    f = ir.Merged("fact")
    d = ir.Merged("dim")
    residual = ir.Aggregate(ir.Join(f, d, "k", "k"), ("g",),
                            (("s", "sum", "v"), ("c", "count", "v")))
    n_dim = 512
    dim = ColumnTable({"k": np.arange(n_dim, dtype=np.int64),
                       "g": rng.integers(0, 32, n_dim).astype(np.int64)})

    def best_of(fn) -> float:
        fn()
        best = np.inf
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    lowest_tensor_win = None
    for size in sorted(sizes, reverse=True):
        fact = ColumnTable({
            "k": rng.integers(0, n_dim, size).astype(np.int64),
            "v": rng.uniform(0.0, 100.0, size)})
        merged = {"fact": fact, "dim": dim}
        execute(residual, merged)  # observe pass (returns the oracle)
        t_interp = best_of(lambda: interpreter.run(residual, merged))
        t_tensor = best_of(lambda: execute(residual, merged))
        if t_interp <= t_tensor:
            break
        lowest_tensor_win = size
    if lowest_tensor_win is None:
        return float("inf")  # tensor never won: auto stays on the oracle
    lower = max((s for s in sizes if s < lowest_tensor_win), default=None)
    return (float(lowest_tensor_win) if lower is None
            else float(np.sqrt(lowest_tensor_win * lower)))


def auto_threshold() -> float:
    """Lazy calibrated crossover for ``EngineConfig.residual="auto"`` —
    deferred to first use (unlike the filter-stage import-time
    calibration) because it jit-compiles a probe program."""
    global _AUTO_THRESHOLD
    if _AUTO_THRESHOLD is not None:
        return _AUTO_THRESHOLD
    env = os.environ.get("REPRO_RESIDUAL_THRESHOLD")
    if env:
        _AUTO_THRESHOLD = float(env)
    elif os.environ.get("REPRO_NO_CALIBRATE"):
        _AUTO_THRESHOLD = float(DEFAULT_RESIDUAL_THRESHOLD)
    else:
        _AUTO_THRESHOLD = calibrate_residual_threshold()
    return _AUTO_THRESHOLD
