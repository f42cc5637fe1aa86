"""The readers of the program's occupancy account (``occupancy_window.py``
and the three ``layer_metrics`` that use it): over a clock filled by hand,
where they read nothing, their entries in ``BENCHMARK.json`` by name, and
on the tiny cells through the harness with a window long enough to hold
three whole slots of the clock.  A CPU run proves that they read and what
they count; never a speed.
"""

import json
import os
import time

import pytest

import harness
import occupancy_window
from pslite_tpu.utils import profiling
from pslite_tpu.utils.profiling import (COMPLETED, ENGINE_OP, KV_OP,
                                        StageClock)
from tiny import cell as _cell, check_metrics

METRICS = {"starved_prelaunch_ms": ("ms", "program_span"),
           "starved_ms": ("ms", "program_span"),
           "ready_at_wait_share": ("%", "program_counter")}
SLOT = 1 << StageClock.SLOT_SHIFT        # ns
STEP = SLOT // 64                        # 16.8 ms: a slot holds 64 whole steps
# Three whole 1.07 s slots lie inside any window of 4.3 s.
SECONDS = 4.6


def _read(name, spans):
    ctx = harness.LayerContext(spans=spans, compiles_in_window=0,
                               reduction=None, least={}, peaks={})
    return harness.load_reader([harness.HERE], name)(ctx)


def _filled(monkeypatch, slots=6, ready_every=4):
    """A clock of its own in the program's place, filled with a closed loop
    of two ops a step from slot 100 on (issue, issue, wait, wait): the
    first launch begins 0.1 ms into a step and lasts 0.3 ms, the last wait
    returns 6 ms in; the pull's wait finds its result ready every
    ``ready_every``-th step.  The spans of the steps from slot 100 on."""
    clock = StageClock()
    monkeypatch.setattr(profiling, "_clock", clock)
    spans = []
    for s in range(-8, slots * 64):                # eight warm steps first
        t0 = 100 * SLOT + s * STEP
        for begin, launch in ((100_000, 300_000), (450_000, 250_000)):
            end = t0 + begin + launch
            clock.note((ENGINE_OP, end, 20_000, 30_000, launch))
            clock.note((KV_OP, end + 17_000, -1, 15_000, -1))
        clock.note((COMPLETED, t0 + 3_005_000,
                    6_000 if s % ready_every == 0 else 2_400_000, 5_000, -1))
        clock.note((COMPLETED, t0 + 6_007_000, 2_990_000, 7_000, -1))
        if s >= 0:
            spans.append((t0 / 1e9, (t0 + 700_000) / 1e9,
                          (t0 + 6_007_000) / 1e9))
    return clock, spans


def test_the_three_readers_over_a_hand_filled_clock(monkeypatch):
    clock, spans = _filled(monkeypatch)
    # A spell a step: from 6 ms into a step to 0.1 ms into the next.
    prelaunch = (STEP - 6_000_000 + 100_000) / 1e6
    assert _read("starved_prelaunch_ms", spans) == pytest.approx(prelaunch)
    assert _read("starved_ms", spans) == pytest.approx(prelaunch + 0.3)
    assert _read("ready_at_wait_share", spans) == pytest.approx(12.5)
    account = occupancy_window.per_step(spans)
    assert account["spells"] == pytest.approx(1.0)
    assert account["completed"] == pytest.approx(2.0)
    assert account["resets"] == 0
    parts = sum(account["starved." + p] for p in profiling.STARVED_PARTS)
    assert parts == pytest.approx(account["starved.prelaunch"])
    # What the spell is made of: the caller's own time, mostly.
    assert account["starved.select"] == pytest.approx(20_000)
    assert account["starved.prep"] == pytest.approx(30_000)
    assert account["starved.complete.copy"] == pytest.approx(7_000)
    assert account["starved.route"] == 0


def test_fewer_than_min_slots_or_no_spans_read_nothing(monkeypatch):
    clock, spans = _filled(monkeypatch, slots=3)   # two whole slots inside
    assert occupancy_window.per_step([]) is None
    assert occupancy_window.per_step(spans[5:-5]) is None
    for name in METRICS:
        assert _read(name, spans[5:-5]) is None
        assert _read(name, []) is None


def test_ps_telemetry_0_reads_nothing(monkeypatch):
    monkeypatch.setattr(profiling, "_clock", None)
    monkeypatch.setenv("PS_TELEMETRY", "0")
    assert not isinstance(profiling.stage_clock(), StageClock)
    now = time.perf_counter()
    spans = [(now - 10.0 + i, now - 9.5 + i, now - 9.0 + i)
             for i in range(10)]
    assert occupancy_window.per_step(spans) is None
    for name in METRICS:
        assert _read(name, spans) is None
    monkeypatch.setattr(profiling, "_clock", None)


@pytest.mark.parametrize("lacks", ["occupancy", "stage_clock"])
def test_a_program_from_before_the_account_reads_nothing(lacks, monkeypatch):
    """The parent of the PR that brought the account has a ``StageClock``
    without ``occupancy`` (and the parent of the clock's PR no
    ``stage_clock``): the readers return nothing and do not raise, and the
    stage readers beside them read as ever."""
    clock, spans = _filled(monkeypatch)
    if lacks == "occupancy":
        monkeypatch.delattr(StageClock, "occupancy")
    else:
        monkeypatch.delattr(profiling, "stage_clock")
    assert occupancy_window.per_step(spans) is None
    for name in METRICS:
        assert _read(name, spans) is None
    want = None if lacks == "stage_clock" else pytest.approx(0.55)
    assert _read("launch_ms", spans) == want


def test_no_op_completed_in_the_window_gives_no_share(monkeypatch):
    clock = StageClock()
    monkeypatch.setattr(profiling, "_clock", clock)
    spans = []
    for s in range(6 * 64):                        # engine ops alone
        t0 = 100 * SLOT + s * STEP
        clock.note((ENGINE_OP, t0 + 400_000, 20_000, 30_000, 300_000))
        spans.append((t0 / 1e9, (t0 + 400_000) / 1e9, (t0 + 500_000) / 1e9))
    assert _read("ready_at_wait_share", spans) is None
    assert _read("starved_ms", spans) == 0.0       # never known to be idle


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entries_are_found_by_name(name, bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    unit, source = METRICS[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "moves": "step_p50",
                     "layer": "app api and engine host side"}
    # No ``workloads``: every cell reports it, and finds its reader.
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], root=bench_root)
        assert name in {m["name"] for m in cell.per_layer}
        assert callable(harness.load_reader(cell.search, name))
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] in
              ("issue_ms", "wait_ms", "issue_exposed_ms", "launch_ms")}
    assert layers == {entry["layer"]}


@pytest.mark.parametrize("kind, ops", [("dense", None), ("sparse", 2)])
def test_a_cpu_rehearsal_reports_the_three(kind, ops, monkeypatch):
    windows = []
    real = harness.run_window

    def run_window(driver, *a, **kw):
        windows.append((driver, real(driver, *a, **kw)))
        return windows[-1][1]

    monkeypatch.setattr(harness, "run_window", run_window)
    ok, result = harness.run_cell(_cell(kind), 17, SECONDS, True,
                                  time.perf_counter(), require_tpu=False)
    assert ok
    check_metrics(result, "per_layer", set(METRICS) | {"issue_ms", "wait_ms"})
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name, (unit, _) in METRICS.items():
        assert result["metrics"][name]["unit"] == unit
    # A closed loop: the process is known idle once a step, from its last
    # wait to the next step's first launch.
    driver, window = windows[0]                    # the profiler-off window
    account = occupancy_window.per_step(window.spans)
    assert account["spells"] == pytest.approx(1.0, abs=0.02)
    if ops is None:
        ops = len(driver.sizes)                    # one push_pull a bucket
    assert account["completed"] == pytest.approx(ops, rel=0.02)
    assert account["resets"] == 0
    assert 0.0 < m["starved_prelaunch_ms"] < m["starved_ms"]
    # The spell lies between two steps' issues and outside every wait.
    step_ms = 1e3 * window.seconds / len(window.spans)
    assert m["starved_ms"] < step_ms
    assert 0.0 <= m["ready_at_wait_share"] <= 100.0
    parts = sum(account["starved." + p] for p in profiling.STARVED_PARTS)
    assert parts == pytest.approx(account["starved.prelaunch"])
