"""From a JAX profiler trace to device busy time, idle gaps and top ops.

``extract`` reads the ``.xplane.pb`` the profiler wrote into a compact
event list (``Events``): the device operations of every accelerator
plane, and the host annotations the harness puts around each query call
(``bench_query:<client>:<n>:<qid>``). Everything after that works on the
compact list, so the reduction is checked on a small recorded trace
(``bench/testdata``) without the chip.

- busy: the union of the device-op intervals, clipped to the traced
  stretch (first annotation start to last annotation end), averaged over
  the devices that ran anything;
- idle gaps: the stretches of that window with no device op, each
  labelled with what the host was doing: the queries whose annotation was
  open at the gap's middle and the ``repro.obs`` spans open then;
- top ops: device time summed per ``<program>/<instruction>`` (a loop's
  time includes the ops of its body, which are listed too).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

ANNOTATION = "bench_query:"
# the lines of a device plane with the operations that ran, and with the
# programs (XLA modules) they ran in
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"

Interval = Tuple[float, float]       # [start, end) in ns, trace clock


@dataclasses.dataclass
class Events:
    ops: Dict[str, List[Tuple[str, float, float]]]   # device -> (name, t, dur)
    annotations: List[Tuple[str, float, float]]      # (name, t, dur)

    def to_json(self) -> Dict:
        return {"ops": self.ops, "annotations": self.annotations}

    @staticmethod
    def from_json(d: Dict) -> "Events":
        return Events({k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                      [tuple(e) for e in d["annotations"]])


def _op_names(ops, modules) -> List[Tuple[str, float, float]]:
    """Each op as ``<program>/<instruction>``: the XLA module running
    when it started, and the op's HLO instruction name without its
    operands (``%fusion.20``)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, t, d in ops:
        i = bisect.bisect_right(starts, t) - 1
        mod = mods[i][0] if i >= 0 and t < mods[i][1] + mods[i][2] else "?"
        out.append((f"{mod}/{name.split(' = ', 1)[0]}", t, d))
    return out


def extract(trace_dir: str) -> Events:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops: Dict[str, List] = {}
    ann: List = []
    for path in paths:
        data = ProfileData.from_file(path)
        for plane in data.planes:
            # host threads share line names ("python"), so lines are
            # read as a list, never keyed by name
            lines = [(ln.name, [(e.name, float(e.start_ns),
                                 float(e.duration_ns)) for e in ln.events])
                     for ln in plane.lines]
            if plane.name.startswith("/device:"):
                op_evs = [e for n, evs in lines if n == OP_LINE for e in evs]
                mod_evs = [e for n, evs in lines if n == MODULE_LINE
                           for e in evs]
                if op_evs:
                    ops.setdefault(plane.name, []).extend(
                        _op_names(op_evs, mod_evs))
                continue
            for _name, evs in lines:
                ann.extend(e for e in evs if e[0].startswith(ANNOTATION))
    return Events(ops, sorted(ann, key=lambda a: a[1]))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi) that no interval of ``busy`` (merged,
    clipped) covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def window(ev: Events) -> Optional[Interval]:
    if not ev.annotations:
        return None
    return (min(a[1] for a in ev.annotations),
            max(a[1] + a[2] for a in ev.annotations))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over devices
    devices: int
    top_ops: List[Tuple[str, float]]   # (name, seconds), most first
    gaps: List[Tuple[float, float]]    # (start_ns, end_ns) of the first
    #                                    device, longest first


def reduce(ev: Events) -> Optional[Reduction]:
    """None when the trace holds no annotated stretch or no device op."""
    win = window(ev)
    devs = {d: e for d, e in ev.ops.items() if e}
    if win is None or not devs:
        return None
    lo, hi = win
    busy_total, per_op = 0.0, {}
    first_gaps: List[Interval] = []
    for i, (dev, evs) in enumerate(sorted(devs.items())):
        merged = union(clip([(t, t + d) for _n, t, d in evs], lo, hi))
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            first_gaps = gaps(merged, lo, hi)
        for name, t, d in evs:
            c = clip([(t, t + d)], lo, hi)
            if c:
                per_op[name] = per_op.get(name, 0.0) + (c[0][1] - c[0][0])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / len(devs) * 1e-9,
        devices=len(devs),
        top_ops=[(n, s * 1e-9 / len(devs)) for n, s in top],
        gaps=sorted(first_gaps, key=lambda g: g[0] - g[1]))


def clock_offset_ns(ev: Events, host_starts: Dict[str, float]
                    ) -> Optional[float]:
    """Trace-clock minus host-clock (``time.perf_counter`` seconds) offset,
    from the annotations whose host start the harness recorded: the
    median of the per-annotation differences."""
    diffs = sorted(t - host_starts[n] * 1e9 for n, t, _d in ev.annotations
                   if n in host_starts)
    return diffs[len(diffs) // 2] if diffs else None


def label_gaps(red: Reduction, ev: Events,
               spans: Sequence[Tuple[str, float, float]],
               offset_ns: Optional[float], n: int = 10
               ) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps as (label, seconds). ``spans`` are
    (name, start, end) in host-clock seconds. The label names the queries
    whose annotation was open at the gap's middle and, with a clock
    offset, the spans open then."""
    out = []
    for s, e in red.gaps[:n]:
        mid = (s + e) / 2
        qids = sorted({a[0].rsplit(":", 1)[-1] for a in ev.annotations
                       if a[1] <= mid < a[1] + a[2]})
        label = ",".join(qids) or "no query"
        if offset_ns is not None:
            host = (mid - offset_ns) * 1e-9
            open_ = sorted({nm for nm, t0, t1 in spans if t0 <= host < t1}
                           - {"run_stream", "query"})
            if open_:
                label += " " + "+".join(open_)
        out.append((label, (e - s) * 1e-9))
    return out


def save(ev: Events, path: str) -> None:
    with open(path, "w") as f:
        json.dump(ev.to_json(), f)


def load(path: str) -> Events:
    with open(path) as f:
        return Events.from_json(json.load(f))
