"""The ``sf1-power`` cell (TPC-H's power test on idle storage), run
without the chip.

One closed-loop client runs stream 00's order over all 15 query types
(traffic ``power``) on the ``tpch-sf1`` deployment (storage power 1, the
process storage tier, the tensor residual), cut to a tiny scale, through
the harness's own path from set-up to the verdict. Every type has to
complete in a traced window, agree with the plain reference and stay on
the jitted path: no fallback, no error, no compile in the window, no
``residual_fallback`` span. Q15's and Q22's PyOp host steps show as
``residual_pyop`` spans, which the cell's ``residual_pyop_ms`` reads.
"""
import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, reference  # noqa: E402

CELL = "sf1-power"
WINDOW_S = 3.0       # a pass of the 15 takes well under a second here


def _tiny():
    """``sf1-power`` as the manifest loads it, cut to a CPU test's size."""
    cell = harness.load_cell(CELL)
    return dataclasses.replace(
        cell, config={**cell.config, "tpch_scale_factor": 0.005,
                      "rows_per_partition": 1_000})


@pytest.fixture(scope="module")
def served_window(tmp_path_factory):
    """One traced run of the tiny power cell: its window and verdict."""
    served = harness.build(_tiny(), 2**35 + 15)
    try:
        harness.warm(served)
        win = harness.serve_window(
            served, WINDOW_S, traced=True,
            trace_dir=str(tmp_path_factory.mktemp("trace")))
    finally:
        harness.free(served)
    return served, win, harness.judge(served, win)


def test_every_query_type_completes_and_agrees_with_the_reference(
        served_window):
    served, win, verdict = served_window
    assert served.cell.config["storage_power"] == 1.0
    assert len(served.cell.streams) == 1
    assert sorted(served.cell.queries) == sorted(reference.QUERIES)
    assert {r.qid for r in win.completed} == set(served.cell.queries)
    assert verdict.correct, verdict.numbers()
    assert verdict.wrong == 0 and verdict.failed == 0
    assert verdict.compared == len(win.records)


def test_the_window_stays_on_the_jitted_path(served_window):
    _served, win, _verdict = served_window
    for c in ("residual.fallbacks", "residual.errors",
              "residual.jit_cache.misses"):
        assert win.counters[c] == 0, c
    assert win.counters["residual.jit_cache.hits"] > 0
    names = {n for n, _t0, _t1 in win.spans}
    assert "residual_fallback" not in names
    assert {"residual_pyop", "residual_compute"} <= names


def test_the_cell_traces_residual_pyop_ms():
    cell = harness.load_cell(CELL)
    assert cell.config["storage_power"] == 1.0
    assert cell.traffic["streams"] == [0]
    assert "residual_pyop_ms" in {m["name"] for m in cell.metrics[True]}
    assert callable(harness.metric_reader("residual_pyop_ms"))


def _window(spans, calls=4):
    recs = [harness.Record(0, n, "Q15", n * 0.1, 1.0) for n in range(calls)]
    win = harness.Window(0.0, 10.0, recs, {})
    win.spans = spans
    return win


def test_residual_pyop_ms_sums_its_span_per_call():
    read = harness.metric_reader("residual_pyop_ms")
    spans = [("residual_pyop", 0.0, 0.002), ("residual_pyop", 1.0, 1.006),
             ("residual_compute", 0.0, 2.0), ("residual_prep", 0.0, 0.5)]
    assert read(harness.Ctx(_window(spans), 0.0)) == pytest.approx(2.0)


def test_residual_pyop_ms_is_silent_without_the_span():
    """A program without the span (the parent's traced window) and an
    untraced window read as nothing rather than 0."""
    read = harness.metric_reader("residual_pyop_ms")
    old = [("residual_compute", 0.0, 0.4), ("residual_prep", 0.0, 0.1)]
    assert read(harness.Ctx(_window(old), 0.0)) is None
    assert read(harness.Ctx(_window(None), 0.0)) is None
