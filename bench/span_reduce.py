"""Per-layer readings from the program's own spans, for spans that an
older program may not emit.

A reader of a span that the program under test lacks returns None, so a
metric that reads what a newer program adds is left out of an older
program's result line instead of reading 0.

``idle_while_open`` measures the device-idle time during which any of a
set of host spans is open. The spans are on the host clock
(``time.perf_counter``); ``on_trace_clock`` places them on the profiler's
clock through the harness's annotation offset
(``trace_reduce.clock_offset_ns``).
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

from bench import trace_reduce
from bench.trace_reduce import Interval


def seconds(ctx, name: str) -> Optional[float]:
    """Summed duration of the traced window's ``name`` spans; None when
    the window holds none (or the run was not traced)."""
    spans = ctx.window.spans
    if not spans or not any(n == name for n, _t0, _t1 in spans):
        return None
    return ctx.span_seconds(name)


def per_call_ms(ctx, name: str) -> Optional[float]:
    return ctx.per_call_ms(seconds(ctx, name))


def idle_while_open(gaps: Sequence[Interval], open_: Sequence[Interval]
                    ) -> float:
    """Length of the intersection of two interval sets: the idle gaps of
    the device and the stretches in which some span is open."""
    a, b = trace_reduce.union(gaps), trace_reduce.union(open_)
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def on_trace_clock(ctx, names: Iterable[str]) -> Optional[List[Interval]]:
    """The traced window's ``names`` spans as [start, end) on the
    profiler's clock (ns); None without a clock offset."""
    ev = ctx.window.events
    if ev is None or ctx.window.spans is None:
        return None
    offset = trace_reduce.clock_offset_ns(ev, {
        f"{trace_reduce.ANNOTATION}{r.client}:{r.n}:{r.qid}": r.t0
        for r in ctx.calls})
    if offset is None:
        return None
    names = set(names)
    return [(t0 * 1e9 + offset, t1 * 1e9 + offset)
            for n, t0, t1 in ctx.window.spans if n in names]


def idle_share_while_open(ctx, names: Iterable[str]) -> Optional[float]:
    """Device-idle time while any ``names`` span is open, in percent of
    the traced stretch (first device)."""
    red = ctx.device
    if red is None or red.window_s <= 0:
        return None
    spans = on_trace_clock(ctx, names)
    if spans is None:
        return None
    lo, hi = trace_reduce.window(ctx.window.events)
    idle = idle_while_open(red.gaps, trace_reduce.clip(spans, lo, hi))
    return 100.0 * idle * 1e-9 / red.window_s
