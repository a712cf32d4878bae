"""The per-layer readers of the residual's step spans, the storage queue
span and the device-idle time while the compute host works, checked on
synthetic windows and traces without the chip.

Each reader returns None where the program lacks its span, so an older
program's traced result line leaves the metric out.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, span_reduce, trace_reduce  # noqa: E402

STEP_READERS = {"residual_prep_ms": "residual_prep",
                "h2d_ms": "residual_h2d",
                "residual_device_ms": "residual_device",
                "d2h_ms": "residual_d2h",
                "storage_queue_ms": "worker_queue"}
NEW_READERS = list(STEP_READERS) + ["idle_compute_host_share"]
OFF = 1000.0          # trace clock minus host clock, ns


def _window(spans, calls=2):
    recs = [harness.Record(0, n, "Q3", n * 50e-9, 1.0) for n in range(calls)]
    win = harness.Window(0.0, 10.0, recs, {})
    win.spans = spans
    return win


def _traced_ctx(spans):
    """Two calls whose annotations span [1000, 1100) ns of the trace;
    the device is busy over [1000, 1020) and [1060, 1080)."""
    win = _window(spans)
    win.events = trace_reduce.Events(
        {"/device:TPU:0": [("a", 1000.0, 20.0), ("b", 1060.0, 20.0)]},
        [(f"{trace_reduce.ANNOTATION}0:0:Q3", 0.0 + OFF, 60.0),
         (f"{trace_reduce.ANNOTATION}0:1:Q3", 50.0 + OFF, 50.0)])
    return harness.Ctx(win, 0.0, device=trace_reduce.reduce(win.events))


def test_every_new_reader_is_in_the_manifest_for_the_busy_cell():
    cell = harness.load_cell("sf1-busy-joins")
    names = {m["name"] for m in cell.metrics[True]}
    assert set(NEW_READERS) <= names
    for name in NEW_READERS:
        assert callable(harness.metric_reader(name))


@pytest.mark.parametrize("metric", sorted(STEP_READERS))
def test_step_readers_sum_their_span_per_call(metric):
    span = STEP_READERS[metric]
    spans = [(span, 0.0, 0.010), (span, 1.0, 1.030),
             ("residual_compute", 0.0, 2.0), ("other", 0.0, 5.0)]
    ctx = harness.Ctx(_window(spans), 0.0)
    assert harness.metric_reader(metric)(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_readers_are_silent_on_a_program_without_the_spans(metric):
    """An older program's traced window (only the spans it had), and an
    untraced window, read as nothing rather than 0."""
    old = [("residual_compute", 0.0, 0.4), ("compute_replay", 0.0, 0.2),
           ("merge", 0.1, 0.2), ("storage_execute", 0.0, 0.3)]
    read = harness.metric_reader(metric)
    assert read(_traced_ctx(old)) is None
    assert read(harness.Ctx(_window(None), 0.0)) is None


def test_idle_while_open_on_known_intervals():
    f = span_reduce.idle_while_open
    assert f([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert f([(0, 10)], [(5, 8), (6, 9), (9, 12)]) == 5
    assert f([(0, 10)], [(10, 20)]) == 0
    assert f([], [(0, 5)]) == 0 and f([(0, 5)], []) == 0
    assert f([(20, 30), (0, 10)], [(0, 100)]) == 20


def test_idle_compute_host_share_counts_only_host_work_spans():
    """Host spans placed on the trace clock through the annotation
    offset: merge and prep overlap over the gap [1020, 1060), d2h ends
    in the gap [1080, 1100); the device span and storage execute are
    not host work of the compute layer."""
    ns = 1e-9
    spans = [("merge", 10 * ns, 40 * ns),
             ("residual_prep", 30 * ns, 45 * ns),
             ("residual_device", 50 * ns, 70 * ns),
             ("residual_d2h", 75 * ns, 90 * ns),
             ("storage_execute", 20 * ns, 100 * ns)]
    ctx = _traced_ctx(spans)
    assert ctx.device.window_s == pytest.approx(100e-9)
    assert harness.metric_reader("device_idle_share")(ctx) == pytest.approx(
        60.0)
    got = harness.metric_reader("idle_compute_host_share")(ctx)
    assert got == pytest.approx(35.0)
    assert span_reduce.on_trace_clock(ctx, ["residual_d2h"]) == [
        (pytest.approx(1075.0), pytest.approx(1090.0))]


def test_idle_share_needs_a_device_trace_and_an_offset():
    spans = [("residual_prep", 0.0, 1e-8)]
    assert harness.metric_reader("idle_compute_host_share")(
        harness.Ctx(_window(spans), 0.0)) is None
    ctx = _traced_ctx(spans)
    ctx.window.records = []           # no call to place the clock by
    assert span_reduce.idle_share_while_open(ctx, ["residual_prep"]) is None
