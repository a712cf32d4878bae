"""Fused batched storage executor: compile-once PushPlans, vectorized
multi-partition execution — including the aux-producing data paths.

The reference path (``core.plan.execute_push_plan``) interprets a
``PushPlan`` per partition: it re-walks the predicate expression tree,
re-derives columns, and re-runs the grouping machinery for every one of the
~160 per-partition requests a query issues. The paper's pushdown wins rest
on the storage-side operator path being tight (PushdownDB; Farview), so
this module lowers each plan **once per query**:

- ``compile_push_plan(plan)`` -> ``CompiledPushPlan``: the predicate is
  compiled to a single numpy kernel (``expressions.compile_expr``, the same
  lowering the Pallas ``predicate_bitmap`` kernel uses), the derive/agg/
  top-k stages are bound into one fused closure, and plan-level invariants
  (``accessed_columns``, the cost model's per-plan constants, the
  selectivity closure) are memoized instead of recomputed per partition.

- ``CompiledPushPlan.execute_batch(tables)`` stacks all partitions of a
  table that share one plan and executes them in a single vectorized pass:
  filter + derive run once over the concatenated columns, and partial
  aggregation uses the partition id as an implicit leading segment key
  (``np.bincount``/``ufunc.reduceat`` over the concatenation), so the
  Python-per-partition loop in ``engine.execute_requests`` collapses to one
  call per (table, plan).

- ``execute_batch_aux`` / ``execute_batch_parts`` additionally emit the
  §4.2 **auxiliary by-products** in the same fused pass: per-partition
  packed selection bitmaps (``bitmap_only`` plans — Figs 3/4), and
  per-partition hash-partition slices + position vectors (``shuffle``
  plans — Fig 5/15). One predicate/hash evaluation over the concatenation
  serves every partition; a single stable sort by ``(partition, target)``
  replaces the reference's ``n_parts * n_targets`` boolean filters.

The filter stage is **selectivity-adaptive**: the compiled ``sel_fn``
estimate (or the exact bitmap popcount on ``apply_bitmap`` plans) picks
between gathering survivors per partition (cheap when the predicate is
selective) and concatenating whole columns then applying one big mask
(cheap when most rows survive — scan-heavy plans used to pay per-partition
gather overhead for nothing). The crossover threshold is micro-calibrated
at import time (``calibrate_gather_threshold``), overridable via
``EngineConfig.filter_gather_threshold`` or ``REPRO_GATHER_THRESHOLD``;
each batch's decision lands in the observability subsystem's bounded
filter-decision channel (``repro.obs.filter_decision_channel``) for the
benchmarks and traces to report. Both branches produce the same bytes —
the choice is purely a performance one.

Bitwise contract: the batch path returns **byte-identical** merged tables
and aux products to the per-partition reference. The load-bearing facts:
elementwise numpy ops distribute over concatenation exactly;
``np.bincount`` accumulates weights in array order (so segment-keyed sums
add the same floats in the same order as per-partition sums); stable
argsort + ``reduceat`` reduce identical segments; a stable sort by
``(partition, target)`` slices into exactly the rows ``pid == target``
selects per partition, in the same order; and the keyless-agg / top-k
stages intentionally drop to a per-segment loop because their reference
semantics (``np.sum`` pairwise summation, ``argpartition`` tie choices,
the empty-partition ``[0.]`` placeholder) are not concatenation-invariant
— those loops run on the already-filtered rows, so the heavy stages stay
fused. ``tests/test_executor.py`` pins all of this against the reference
oracle.
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.cost import RequestCost
from repro.core.plan import _AGG_OUT_ROWS, PushPlan
from repro.obs import trace as obs_trace
from repro.core.plan import batchable_stages  # noqa: F401 re-export
from repro.queryproc import expressions as ex
from repro.queryproc import operators as ops
from repro.queryproc.table import ColumnTable
from repro.storage.catalog import Partition

# real-execution path names (shared by engine and runtime)
EXECUTOR_BATCHED = "batched"      # compile-once plans, one pass per table
EXECUTOR_REFERENCE = "reference"  # per-partition interpretive oracle

# --------------------------------------------- adaptive filter calibration
DEFAULT_GATHER_THRESHOLD = 0.55  # fallback when calibration is disabled


def calibrate_gather_threshold(n_parts: int = 160, rows_per_part: int = 1000,
                               n_cols: int = 3,
                               sels: Sequence[float] = (0.9, 0.7, 0.5, 0.3),
                               repeats: int = 2) -> float:
    """Micro-benchmark the two filter-stage strategies at the engine's real
    request shape (~160 small partitions) and return the estimated-
    selectivity crossover above which concat-everything beats
    gather-survivors on this machine.

    gather copies ~sel*N bytes through ``n_parts`` cache-resident boolean
    gathers; concat copies ~(1+sel)*N bytes in two big bandwidth-bound ops
    — the crossover is machine-dependent (allocator + memcpy throughput vs
    per-call overhead), hence measured, not assumed. The scan walks the
    selectivities DOWNWARD and stops at the first one where gather wins, so
    a noisy concat win at low selectivity can never drag the threshold down
    — the adaptive stage must never lose to the always-gather baseline."""
    rng = np.random.default_rng(0)
    n_rows = n_parts * rows_per_part
    # one shared buffer stands in for every column: the strategies only
    # read the sources (outputs are fresh allocations either way), so the
    # work profile is identical and data generation stays cheap at import
    base = rng.uniform(0.0, 1.0, n_rows)
    data = [base] * n_cols
    bnd = np.linspace(0, n_rows, n_parts + 1).astype(np.intp)
    parts = [[a[bnd[p]:bnd[p + 1]] for a in data] for p in range(n_parts)]
    u = rng.random(n_rows)

    def best_of(fn) -> float:
        fn()  # warm
        return min(_t(fn) for _ in range(repeats))

    def _t(fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    lowest_concat_win = None
    for sel in sorted(sels, reverse=True):
        mask = u < sel
        masks = [mask[bnd[p]:bnd[p + 1]] for p in range(n_parts)]
        t_gather = best_of(lambda: [np.concatenate(
            [parts[p][i][masks[p]] for p in range(n_parts)])
            for i in range(n_cols)])
        t_concat = best_of(lambda: [np.concatenate(
            [parts[p][i] for p in range(n_parts)])[mask]
            for i in range(n_cols)])
        if t_concat >= t_gather:
            break
        lowest_concat_win = sel
    if lowest_concat_win is None:
        return 1.01  # gather always won: never switch
    lower = max((s for s in sels if s < lowest_concat_win), default=None)
    return (lowest_concat_win if lower is None
            else (lowest_concat_win + lower) / 2)


def _init_threshold() -> float:
    env = os.environ.get("REPRO_GATHER_THRESHOLD")
    if env:
        return float(env)
    if os.environ.get("REPRO_NO_CALIBRATE"):
        return DEFAULT_GATHER_THRESHOLD
    try:
        return calibrate_gather_threshold()
    except Exception:  # pragma: no cover - calibration is best-effort
        return DEFAULT_GATHER_THRESHOLD


FILTER_GATHER_THRESHOLD = _init_threshold()

# Batch filter-stage decisions live in the observability subsystem's
# bounded, thread-safe channel (repro.obs.filter_decision_channel); these
# wrappers are the executor's surface over it.


def reset_filter_decisions() -> None:
    obs_trace.filter_decision_channel().clear()


def filter_decision_counts() -> Dict[str, int]:
    counts = obs_trace.filter_decision_channel().counts("branch")
    return {"gather": counts.get("gather", 0),
            "concat": counts.get("concat", 0)}


def _record_decision(table: str, est: Optional[float], branch: str,
                     n_parts: int, rows: int) -> None:
    obs_trace.record_filter_decision(table, est, branch, n_parts, rows)


@dataclasses.dataclass
class CompiledPushPlan:
    """A PushPlan lowered once: compiled kernels + memoized invariants."""
    plan: PushPlan
    accessed: Tuple[str, ...]               # memoized plan.accessed_columns()
    pred_fn: Optional[Callable]             # fused numpy predicate kernel
    pred_cols: Tuple[str, ...]              # columns the predicate reads
    sel_fn: Optional[Callable]              # compiled selectivity estimator
    agg_spec: Optional[Dict[str, Tuple[str, str]]]  # out -> (fn, col)
    having_fn: Optional[Callable] = None    # post-agg filter kernel
    having_sel_fn: Optional[Callable] = None  # its selectivity estimator
    # cost-model per-plan constants (plan.estimate_cost recomputes these
    # per partition; only the stats lookups actually vary across partitions)
    _n_derived_out: int = 0
    _agg_keys: Tuple[str, ...] = ()

    # ------------------------------------------------------------ execution
    def raw_projection(self, data: ColumnTable) -> ColumnTable:
        """The pushback payload: the raw accessed-column projection of one
        partition — the paper's ``S_in``. Executing this plan over the
        projection is byte-identical to executing it over the full
        partition (output columns ⊆ accessed ∪ derived), which is what
        lets the compute layer replay the same compiled plan."""
        return data.select([c for c in self.accessed if c in data.cols])

    def execute(self, data: ColumnTable, bitmap: Optional[np.ndarray] = None
                ) -> Tuple[ColumnTable, Dict]:
        """Single-partition fused path: the same ``(result, aux)`` as
        ``plan.execute_push_plan`` — aux-producing plans (bitmap_only,
        shuffle) emit their by-products from the batch machinery."""
        merged, aux = self.execute_batch_aux(
            [data], None if bitmap is None else [bitmap])
        return merged, aux[0]

    def execute_batch(self, tables: Sequence[ColumnTable],
                      bitmaps: Optional[Sequence[np.ndarray]] = None,
                      threshold: Optional[float] = None,
                      cache=None, parts: Optional[Sequence] = None
                      ) -> ColumnTable:
        """All partitions sharing this plan in one vectorized pass.
        Returns the merged table — byte-identical to
        ``ColumnTable.concat([execute_push_plan(plan, t)[0] for t in tables])``.

        With ``cache`` (a ``core.result_cache.ResultCache``) and ``parts``
        (the matching catalog ``Partition`` per table), cached partitions
        are served and *skipped* in the vectorized pass; only the misses
        run, and their outputs are spliced back in original partition
        order — byte-identical because the fused pass's per-partition
        outputs are batch-composition-invariant (pinned by
        tests/test_executor.py)."""
        out, _, _ = self._run_batch(tables, bitmaps, threshold,
                                    want_aux=False, cache=cache, parts=parts)
        return out

    def execute_batch_aux(self, tables: Sequence[ColumnTable],
                          bitmaps: Optional[Sequence[np.ndarray]] = None,
                          threshold: Optional[float] = None,
                          cache=None, parts: Optional[Sequence] = None
                          ) -> Tuple[ColumnTable, List[Dict]]:
        """(merged table, per-partition aux dicts) — each aux dict is
        byte-identical to ``execute_push_plan(plan, tables[i])[1]``:
        ``bitmap`` (packed uint32 words) for bitmap_only plans,
        ``shuffle_parts`` + ``position_vector`` for shuffle plans. A
        cache-served partition's aux additionally carries a ``"cache"``
        marker (``"exact"``/``"containment"``)."""
        out, _, aux = self._run_batch(tables, bitmaps, threshold,
                                      want_aux=True, cache=cache,
                                      parts=parts)
        return out, aux

    def execute_batch_parts(self, tables: Sequence[ColumnTable],
                            bitmaps: Optional[Sequence[np.ndarray]] = None,
                            threshold: Optional[float] = None,
                            cache=None, parts: Optional[Sequence] = None
                            ) -> Tuple[List[ColumnTable], List[Dict]]:
        """(per-partition result tables, per-partition aux dicts) — each
        entry byte-identical to ``execute_push_plan(plan, tables[i])``. The
        per-partition views slice one fused pass; nothing is re-executed."""
        out, bounds, aux = self._run_batch(tables, bitmaps, threshold,
                                           want_aux=True, cache=cache,
                                           parts=parts)
        out_parts = [ColumnTable({c: v[bounds[p]:bounds[p + 1]]
                                  for c, v in out.cols.items()})
                     for p in range(len(tables))]
        return out_parts, aux

    def _run_batch(self, tables: Sequence[ColumnTable],
                   bitmaps: Optional[Sequence[np.ndarray]],
                   threshold: Optional[float], want_aux: bool,
                   cache=None, parts: Optional[Sequence] = None
                   ) -> Tuple[ColumnTable, np.ndarray, List[Dict]]:
        """The fused pass. Returns (merged, per-partition output-row bounds
        (n_parts+1,), per-partition aux dicts)."""
        if cache is not None and parts is not None \
                and not self.plan.apply_bitmap:
            return self._run_batch_cached(tables, threshold, cache, parts)
        plan = self.plan
        assert plan.columns or plan.agg is not None, \
            "plans must declare output columns (the splitter guarantees it)"
        n_parts = len(tables)
        lens = np.asarray([len(t) for t in tables], np.int64)

        def concat(column: str) -> np.ndarray:
            if n_parts == 1:
                return np.asarray(tables[0].cols[column])
            return np.concatenate([t.cols[column] for t in tables])

        # accessed columns only: the reference filters whole partitions,
        # but output columns are always a subset of accessed + derived
        present = [c for c in self.accessed if c in tables[0].cols]

        # ---- filter stage: one fused predicate pass over the predicate
        # columns; remaining columns materialize through the adaptive
        # gather-vs-concat branch below
        cols: Dict[str, np.ndarray] = {}
        masks: Optional[List[np.ndarray]] = None
        mask_full: Optional[np.ndarray] = None
        est: Optional[float] = None
        if plan.apply_bitmap:
            assert bitmaps is not None, "compute-layer bitmaps required"
            masks = [ops.unpack_bitmap(w, int(m))
                     for w, m in zip(bitmaps, lens)]
            mask_full = masks[0] if n_parts == 1 else np.concatenate(masks)
            total = int(lens.sum())
            # the bitmap is in hand: the selectivity is exact, not estimated
            est = float(mask_full.sum()) / total if total else 0.0
        elif self.pred_fn is not None:
            pcols = {c: concat(c) for c in self.pred_cols
                     if c in tables[0].cols}
            mask_full = self.pred_fn(pcols)
            masks = (np.split(mask_full, np.cumsum(lens)[:-1]) if n_parts > 1
                     else [mask_full])
            # predicate columns are already concatenated: one gather
            cols = {c: v[mask_full] for c, v in pcols.items() if c in present}
            if self.sel_fn is not None:
                est = float(self.sel_fn(tables[0].stats()))

        segmented = plan.agg is not None or plan.top_k is not None
        if masks is None:
            counts = lens
            seg = np.repeat(np.arange(n_parts), lens) if segmented else None
            for c in present:
                cols.setdefault(c, concat(c))
        else:
            counts = np.asarray([int(m.sum()) for m in masks], np.int64)
            seg = np.repeat(np.arange(n_parts), counts) if segmented else None
            missing = [c for c in present if c not in cols]
            if missing:
                thr = (FILTER_GATHER_THRESHOLD if threshold is None
                       else threshold)
                branch = ("concat" if est is not None and est >= thr
                          else "gather")
                _record_decision(plan.table, est, branch, n_parts,
                                 int(lens.sum()))
                if branch == "concat":
                    # most rows survive: two big copies beat n_parts gathers
                    for c in missing:
                        cols[c] = concat(c)[mask_full]
                else:
                    # selective predicate: copy only the survivors
                    for c in missing:
                        cols[c] = (tables[0].cols[c][masks[0]]
                                   if n_parts == 1 else np.concatenate(
                                       [t.cols[c][m]
                                        for t, m in zip(tables, masks)]))

        # ---- derive stage (fused: one elementwise pass per derived column)
        for name, incols, fn in plan.derive:
            cols[name] = fn(*[cols[c] for c in incols])

        t = ColumnTable(cols)
        if plan.agg is not None:
            # aggregation collapses rows: seg is re-derived at group level
            # so a downstream top-k segments the agg *output*, not the input
            out, seg = self._batched_agg(t, seg, n_parts)
            if self.having_fn is not None:
                # post-agg filter over the partial aggregate's output; seg
                # stays sorted under the mask so bounds/top-k still apply
                hm = self.having_fn(out.cols)
                out = ColumnTable({c: v[hm] for c, v in out.cols.items()})
                seg = np.asarray(seg)[hm]
        elif plan.columns:
            out = t.select([c for c in plan.columns if c in t.cols])
        else:
            out = t
        if plan.top_k is not None:
            out, bounds = self._segmented_top_k(out, seg, n_parts)
        elif plan.agg is not None:
            bounds = np.searchsorted(seg, np.arange(n_parts + 1))
        else:
            bounds = np.concatenate([[0], np.cumsum(counts)])

        aux: List[Dict] = [{} for _ in range(n_parts)]
        if want_aux:
            self._emit_aux(out, bounds, masks, aux)
        return out, bounds, aux

    def _run_batch_cached(self, tables: Sequence[ColumnTable],
                          threshold: Optional[float], cache,
                          parts: Sequence
                          ) -> Tuple[ColumnTable, np.ndarray, List[Dict]]:
        """Serve cached partitions, run the fused pass over the misses
        only, fill the cache from their bounds-sliced outputs, and splice
        everything back in original partition order.

        ``merged == concat(per-partition outputs)`` holds for every plan
        type (the batch path's contract vs the per-partition reference),
        so the spliced merge is byte-identical to the uncached batch —
        including when the miss subset runs as its own smaller batch,
        because per-partition outputs are batch-composition-invariant."""
        assert len(parts) == len(tables)
        n = len(tables)
        res: List[Optional[ColumnTable]] = [None] * n
        auxs: List[Dict] = [{} for _ in range(n)]
        miss: List[int] = []
        for i, part in enumerate(parts):
            hit = cache.serve(self, part)
            if hit is None:
                miss.append(i)
            else:
                res[i], auxs[i] = hit[0], hit[1]
        if miss:
            sub = [tables[i] for i in miss]
            out, bounds, aux = self._run_batch(sub, None, threshold,
                                               want_aux=True)
            for j, i in enumerate(miss):
                r = ColumnTable({c: v[bounds[j]:bounds[j + 1]]
                                 for c, v in out.cols.items()})
                res[i] = r
                auxs[i] = aux[j]
                cache.put(self, parts[i], r, aux[j])
        merged = ColumnTable.concat(res) if n > 1 else res[0]
        out_bounds = np.concatenate(
            [[0], np.cumsum([len(r) for r in res])]).astype(np.int64)
        return merged, out_bounds, auxs

    def _emit_aux(self, out: ColumnTable, bounds: np.ndarray,
                  masks: Optional[List[np.ndarray]], aux: List[Dict]) -> None:
        """The §4.2 by-products, vectorized over the whole batch."""
        plan = self.plan
        n_parts = len(aux)
        if plan.bitmap_only and masks is not None and not plan.apply_bitmap:
            # the reference packs the full-partition predicate mask — which
            # is exactly the per-partition split of the batch mask
            for a, m in zip(aux, masks):
                a["bitmap"] = ops.pack_bitmap(m)
        if plan.shuffle is not None:
            key, n_t = plan.shuffle
            pid = ops.hash_partition_ids(np.asarray(out.cols[key]), n_t)
            seg_of_row = np.repeat(np.arange(n_parts), np.diff(bounds))
            code = seg_of_row * n_t + pid
            order = np.argsort(code, kind="stable")
            # one gather per column; a stable sort by (partition, target)
            # makes each (p, t) run exactly the rows `pid == t` selects per
            # partition, in the reference's row order
            sorted_cols = {c: v[order] for c, v in out.cols.items()}
            bb = np.searchsorted(code[order],
                                 np.arange(n_parts * n_t + 1))
            for p, a in enumerate(aux):
                a["shuffle_parts"] = [
                    ColumnTable({c: v[bb[p * n_t + i]:bb[p * n_t + i + 1]]
                                 for c, v in sorted_cols.items()})
                    for i in range(n_t)]
                a["position_vector"] = pid[bounds[p]:bounds[p + 1]]

    # ----------------------------------------------------- agg / top-k
    def _batched_agg(self, t: ColumnTable, seg: np.ndarray, n_parts: int
                     ) -> Tuple[ColumnTable, np.ndarray]:
        """Returns (partials table, per-output-row partition id)."""
        keys, _ = self.plan.agg
        if keys:
            return self._segment_keyed_agg(t, seg, keys)
        # keyless (scalar) aggs: the reference emits one row per partition,
        # with np.sum's pairwise summation and a float64 [0.] placeholder
        # for empty partitions — neither is concatenation-invariant, so
        # reduce per segment over the already-filtered rows
        bounds = np.searchsorted(seg, np.arange(n_parts + 1))
        out: Dict[str, List[np.ndarray]] = {name: [] for name in self.agg_spec}
        for p in range(n_parts):
            lo, hi = int(bounds[p]), int(bounds[p + 1])
            for name, (fn, col) in self.agg_spec.items():
                if hi == lo:
                    val = np.asarray([0], np.float64)
                elif fn == "count":  # length-only: no column materialization
                    val = np.asarray([np.asarray(hi - lo, np.int64)])
                else:
                    arr = (t.cols[col] if col else next(iter(t.cols.values())))
                    val = np.asarray([ops.AGG_FUNCS[fn](arr[lo:hi])])
                out[name].append(val)
        return (ColumnTable({n: np.concatenate(v) for n, v in out.items()}),
                np.arange(n_parts))  # one output row per partition

    def _segment_keyed_agg(self, t: ColumnTable, seg: np.ndarray,
                           keys: Tuple[str, ...]
                           ) -> Tuple[ColumnTable, np.ndarray]:
        """Grouped partials over all partitions at once, the partition id as
        implicit leading segment key.

        The reference (``ops.grouped_agg`` per partition) sorts a rec array
        — a void-dtype comparison per element. Here one type-specialized
        stable ``np.lexsort`` over (pid, keys...) orders the concatenation;
        group boundaries fall out of adjacent-row key changes. Sorting by
        pid first makes the group order *identical* to concatenating the
        per-partition key-sorted outputs, and sums/counts go through
        ``np.bincount`` over the original-order group ids, so each group
        accumulates the same floats in the same order as the reference —
        bitwise-identical partials (reduceat is pairwise, bincount is
        sequential: only bincount matches)."""
        key_arrs = [t.cols[k] for k in keys]
        n = len(seg)
        # lexsort: last key is primary -> (seg, k1, .., kn) lexicographic
        order = np.lexsort(tuple(reversed(key_arrs)) + (seg,))
        sorted_keys = [a[order] for a in [seg, *key_arrs]]
        new_group = np.zeros(n, bool)
        if n:
            new_group[0] = True
        for a in sorted_keys:
            new_group[1:] |= a[1:] != a[:-1]
        starts = np.flatnonzero(new_group)           # sorted-domain offsets
        n_groups = len(starts)
        gid = np.cumsum(new_group) - 1               # sorted-domain group id
        inv = np.empty(n, np.intp)
        inv[order] = gid                             # original-order group id
        first_idx = order[starts]                    # stable: first original row
        counts = np.bincount(inv, minlength=n_groups)
        out = {k: t.cols[k][first_idx] for k in keys}
        for name, (fn, col) in self.agg_spec.items():
            if fn == "count":
                out[name] = counts.astype(np.int64)
            elif fn == "sum":
                out[name] = np.bincount(inv, weights=t.cols[col].astype(np.float64),
                                        minlength=n_groups)
            elif fn == "mean":
                s = np.bincount(inv, weights=t.cols[col].astype(np.float64),
                                minlength=n_groups)
                out[name] = s / np.maximum(counts, 1)
            else:
                red = np.minimum if fn == "min" else np.maximum
                out[name] = red.reduceat(t.cols[col][order], starts)
        return ColumnTable(out), sorted_keys[0][starts]  # per-group pid

    def _segmented_top_k(self, t: ColumnTable, seg: np.ndarray, n_parts: int
                         ) -> Tuple[ColumnTable, np.ndarray]:
        # per-partition top-k supersets, exactly as the reference selects
        # them (argpartition tie behavior is position-dependent, so the
        # reference operator runs per segment — on filtered rows only)
        col, k, asc = self.plan.top_k
        bounds = np.searchsorted(seg, np.arange(n_parts + 1))
        parts = [ops.top_k(
            ColumnTable({c: v[bounds[p]:bounds[p + 1]]
                         for c, v in t.cols.items()}), col, k, asc)
            for p in range(n_parts)]
        out_bounds = np.concatenate(
            [[0], np.cumsum([len(p) for p in parts])])
        return ColumnTable.concat(parts), out_bounds

    # ------------------------------------------------------------ cost
    def estimate_cost(self, part: Partition) -> RequestCost:
        """Identical arithmetic to ``plan.estimate_cost`` with the per-plan
        constants memoized; only the stats lookups touch the partition."""
        plan = self.plan
        data = part.data
        stats = data.stats()
        acc_cols = [c for c in self.accessed if c in data.cols]
        s_in = data.nbytes(acc_cols, stored=True)
        raw_in = data.nbytes(acc_cols, stored=False)
        sel = self.sel_fn(stats) if self.sel_fn is not None else 1.0
        if plan.bitmap_only:
            out_cols = [c for c in plan.columns if c in data.cols]
            s_out = ((data.nbytes(out_cols, stored=False)
                      + 8 * self._n_derived_out * len(data)) * sel
                     + len(data) / 8)
        elif plan.agg is not None:
            groups = 1
            for key in self._agg_keys:
                groups *= max(1, stats[key].ndv if key in stats
                              else _AGG_OUT_ROWS)
            groups = min(groups, _AGG_OUT_ROWS, len(data))
            s_out = groups * 8 * (len(self._agg_keys) + len(self.agg_spec))
            if self.having_sel_fn is not None:
                s_out *= self.having_sel_fn(stats)
        else:
            out_cols = [c for c in plan.columns if c in data.cols]
            s_out = (data.nbytes(out_cols, stored=False)
                     + 8 * self._n_derived_out * len(data)) * sel
        if plan.top_k is not None:
            s_out = min(s_out, plan.top_k[1] * 8 * max(1, len(plan.columns)))
        return RequestCost(s_in=int(s_in), s_out=int(max(64, s_out)),
                           compute_in=int(raw_in))


# ----------------------------------------------------------- compile cache
_CACHE: "OrderedDict[int, CompiledPushPlan]" = OrderedDict()
_CACHE_CAP = 256


def compile_push_plan(plan: PushPlan) -> CompiledPushPlan:
    """Lower a PushPlan once; memoized per plan object (the engine issues
    one plan instance per (query, table) shared by all its partitions)."""
    hit = _CACHE.get(id(plan))
    if hit is not None and hit.plan is plan:   # guard against id() reuse
        _CACHE.move_to_end(id(plan))
        return hit
    derived = frozenset(n for n, _, _ in plan.derive)
    cplan = CompiledPushPlan(
        plan=plan,
        accessed=plan.accessed_columns(),
        pred_fn=(ex.compile_expr(plan.predicate)
                 if plan.predicate is not None and not plan.apply_bitmap
                 else None),
        pred_cols=(tuple(sorted(ex.columns_of(plan.predicate)))
                   if plan.predicate is not None and not plan.apply_bitmap
                   else ()),
        sel_fn=(ex.compile_selectivity(plan.predicate)
                if plan.predicate is not None else None),
        agg_spec=({o: (f, c) for o, f, c in plan.agg[1]}
                  if plan.agg is not None else None),
        having_fn=(ex.compile_expr(plan.having)
                   if plan.having is not None else None),
        having_sel_fn=(ex.compile_selectivity(plan.having)
                       if plan.having is not None else None),
        _n_derived_out=len(derived & set(plan.columns)),
        _agg_keys=tuple(plan.agg[0]) if plan.agg is not None else (),
    )
    _CACHE[id(plan)] = cplan
    while len(_CACHE) > _CACHE_CAP:
        _CACHE.popitem(last=False)
    return cplan
