"""The readers of the program's own stage clock (``stage_window.py`` and
the seven ``layer_metrics`` that use it), on the tiny cells through the
harness with a window long enough to hold three whole slots of the clock.
A CPU run proves that they read, add up and count; never a speed.
"""

import statistics
import time

import pytest

import harness
import stage_window
from tiny import cell as _cell, check_metrics

STAGE_METRICS = {"route_ms", "select_ms", "prep_ms", "launch_ms",
                 "dispatch_ms", "complete_ms", "ops_per_step"}
ISSUING = ("route_ms", "select_ms", "prep_ms", "launch_ms", "dispatch_ms")
# Three whole 1.07 s slots lie inside any window of 4.3 s.
SECONDS = 4.6


@pytest.mark.parametrize("kind, ops", [("dense", None), ("sparse", 2)])
def test_stage_metrics_add_up_to_issue_ms_and_count_the_ops(kind, ops,
                                                            monkeypatch):
    windows = []
    real = harness.run_window

    def run_window(driver, *a, **kw):
        windows.append((driver, real(driver, *a, **kw)))
        return windows[-1][1]

    monkeypatch.setattr(harness, "run_window", run_window)
    ok, result = harness.run_cell(_cell(kind), 13, SECONDS, True,
                                  time.perf_counter(), require_tpu=False)
    assert ok
    m = {k: v["value"] for k, v in result["metrics"].items()}
    want = STAGE_METRICS | {"issue_ms", "wait_ms", "compiles_in_window"}
    if kind == "sparse":
        want = want - {"route_ms"}      # a sparse call routes nothing
    check_metrics(result, "per_layer", want)
    assert all(m[k] >= 0 for k in want)
    # The stages are means over the window, ``issue_ms`` is its median:
    # beside the mean they add up, beside the median a few slow steps of
    # a CPU run show.  What is left of the issue time is the driver's own
    # loop around the calls (it drops the arrays the step before pulled,
    # one a bucket) and the Python between the stamps of two layers.  On
    # the chip the stages read 89.5-89.9% of ``issue_ms`` in the dense
    # cells (PERF.md §5); here the virtual devices compute on the cores
    # the host's Python runs on, and the dense cell read 88-91%.
    driver, window = windows[0]         # the profiler-off window
    issue = statistics.mean(s[1] - s[0] for s in window.spans) * 1e3
    issuing = sum(m.get(k, 0.0) for k in ISSUING)
    assert 0.8 * issue <= issuing <= 1.0 * issue, (issue, m)
    assert 0.7 * m["issue_ms"] <= issuing <= 1.2 * m["issue_ms"], m
    if ops is None:
        ops = len(driver.sizes)         # one push_pull a bucket
    assert m["ops_per_step"] == pytest.approx(ops, rel=0.01)
    for k in m:
        if k in STAGE_METRICS:
            unit = "count" if k == "ops_per_step" else "ms"
            assert result["metrics"][k]["unit"] == unit


def test_a_short_window_reads_nothing():
    now = time.perf_counter()
    assert stage_window.per_step([]) is None
    assert stage_window.per_step([(now - 0.3, now - 0.2, now)]) is None
    assert stage_window.stage_ms([(now - 0.3, now - 0.2, now)],
                                 "launch") is None


def test_a_program_without_the_clock_reads_nothing(monkeypatch):
    """The parent of the PR that brought the clock has no ``stage_clock``:
    the readers return nothing and do not raise."""
    from pslite_tpu.utils import profiling

    monkeypatch.delattr(profiling, "stage_clock")
    now = time.perf_counter()
    spans = [(now - 10.0 + i, now - 9.5 + i, now - 9.0 + i)
             for i in range(10)]
    assert stage_window.per_step(spans) is None
    for name in STAGE_METRICS:
        read = harness.load_reader([harness.HERE], name)
        ctx = harness.LayerContext(spans=spans, compiles_in_window=0,
                                   reduction=None, least={}, peaks={})
        assert read(ctx) is None
