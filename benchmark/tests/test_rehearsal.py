"""Each kind of cell end to end at a tiny size, without the chip: the
harness's own functions on four virtual CPU devices (the four-chip path)
and, in a child process, on one.  ``run.py`` itself refuses a process
without a TPU and has no size option; the tiny configuration and traffic
files under ``cells/`` are named by no entry of ``workloads``.  The kind
``row-adagrad`` is the room a later PR has: its driver exists only as a file
under ``cells/drivers/`` (a subclass of the sparse driver, taken through the
harness's loader) with a reference of its own beside it.

Also the two tests "How correct is decided" asks for: the lower-precision
control comes out as not correct, and a run whose timed path is broken
underneath comes out with ``correct`` false.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness
from conftest import BENCH, HERE, ROOT
from tiny import cell as _cell, check_metrics

def _run(kind, seed=7, seconds=0.3, trace=False, **kw):
    return harness.run_cell(_cell(kind), seed, seconds, trace,
                            time.perf_counter(), require_tpu=False, **kw)


@pytest.mark.parametrize("kind", ["dense", "sparse", "row-adagrad"])
def test_cell_end_to_end_on_four_devices(kind, capsys):
    ok, result = _run(kind, seed=2**31 + 5)   # more than 32 signed bits hold
    out = capsys.readouterr().out
    assert ok and result["correct"] and result["failed"] == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    check_metrics(result, "end_to_end", {"goodput", "step_p50", "step_p95",
                                         "setup_s"})
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["count"] == 4
    assert result["attempted"] >= 1
    assert "0 compilations in the window" in out
    assert "compare first3_err" in out and "compare final_err" in out
    assert "compare engine_byte_counters_gap: 0.0" in out


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_traced_run_reports_what_it_can_read(kind):
    """A CPU trace has no TPU plane: the trace readers find nothing and
    their metrics are left out; the host spans and the counter are read."""
    ok, result = _run(kind, trace=True)
    assert ok
    check_metrics(result, "per_layer", {"issue_ms", "wait_ms",
                                        "compiles_in_window"})
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert "breakdown" not in result and "busy_s" not in result["device"]


@pytest.mark.parametrize("kind", ["dense", "sparse", "row-adagrad"])
def test_same_seed_same_inputs_and_control_fails(kind, capsys):
    """The bf16 control is not correct in either number, by a wide margin
    over what the program reads (the limits of the tiny cells are 1e-3)."""
    _run(kind, seed=11, control="bf16")
    first = capsys.readouterr().out
    for number in ("first3_err", "final_err"):
        line = next(l for l in first.splitlines()
                    if l.startswith(f"control[bf16] {number}"))
        assert "fails, as it must" in line
        sound = next(l for l in first.splitlines()
                     if l.startswith(f"compare {number}"))
        assert float(line.split()[2]) > 30 * float(sound.split()[2])


def test_one_device_in_a_child_process():
    code = (
        "import json, os, sys, time\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'\n"
        "os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'\n"
        f"sys.path[:0] = [{BENCH!r}, {ROOT!r}, {HERE!r}]\n"
        "import harness, tiny\n"
        "for kind in ('dense', 'sparse'):\n"
        "    cell = tiny.cell(kind, chips=1)\n"
        "    ok, r = harness.run_cell(cell, 3, 0.2, False, time.perf_counter(),"
        " require_tpu=False)\n"
        "    assert ok and r['device']['count'] == 1, r\n"
        "print('ONE_DEVICE_OK')\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "PS_LOOPBACK_NS"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert "ONE_DEVICE_OK" in out.stdout, out.stderr[-3000:]


def test_wrong_number_of_devices_is_refused():
    with pytest.raises(harness.NoDevice, match="asks for 1"):
        harness.run_cell(_cell("dense", chips=1), 1, 0.1, False,
                         time.perf_counter(), require_tpu=False)


def test_run_py_refuses_a_process_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "bert-large-adam.device", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""          # no result line
    assert "not a TPU" in out.stderr


# -- the timed path broken underneath --------------------------------------


def _break_dense_unchanged_state(monkeypatch):
    """A step that returns its state unchanged: one bucket's push_pull
    hands back the parameters it had, and applies nothing."""
    from pslite_tpu.parallel.engine import CollectiveEngine

    real = CollectiveEngine.push_pull
    hits = []

    def push_pull(self, name, grads, *a, **kw):
        if name == "b0" and len(hits) >= 3:   # sound through the checked steps
            return self._stores[name][: self._buckets[name].total_len]
        if name == "b0":
            hits.append(1)
        return real(self, name, grads, *a, **kw)

    monkeypatch.setattr(CollectiveEngine, "push_pull", push_pull)


def _break_dense_drop_a_worker(monkeypatch):
    """The exchange leaves out a part of the batch: one worker's row of
    every bucket is zeroed before the sum."""
    from pslite_tpu.parallel.engine import CollectiveEngine

    real = CollectiveEngine.push_pull

    def push_pull(self, name, grads, *a, **kw):
        return real(self, name, grads.at[0].set(0.0), *a, **kw)

    monkeypatch.setattr(CollectiveEngine, "push_pull", push_pull)


def _break_sparse_altered_answer(monkeypatch):
    """An answer altered where it is produced: pulled rows come back
    scaled by (1 + 1e-2), the error of a bf16 table."""
    from pslite_tpu.parallel.sparse import SparseEngine

    real = SparseEngine.pull

    def pull(self, name, indices):
        return real(self, name, indices) * np.float32(1.01)

    monkeypatch.setattr(SparseEngine, "pull", pull)


def _break_sparse_lost_push(monkeypatch):
    """Every fifth push is acknowledged and not applied."""
    from pslite_tpu.parallel.sparse import SparseEngine

    real = SparseEngine.push
    calls = []

    def push(self, name, indices, grads, *a, **kw):
        calls.append(1)
        if len(calls) % 5 == 0:
            return self._stores[name][:1, :1]
        return real(self, name, indices, grads, *a, **kw)

    monkeypatch.setattr(SparseEngine, "push", push)


@pytest.mark.parametrize("kind, breaker, number", [
    ("dense", _break_dense_unchanged_state, "final_err"),
    ("dense", _break_dense_drop_a_worker, "first3_err"),
    ("sparse", _break_sparse_altered_answer, "first3_err"),
    ("sparse", _break_sparse_lost_push, "final_err"),
    ("row-adagrad", _break_sparse_lost_push, "final_err"),
])
def test_a_broken_timed_path_is_not_correct(kind, breaker, number,
                                            monkeypatch, capsys):
    breaker(monkeypatch)
    ok, result = _run(kind, seed=5)
    out = capsys.readouterr().out
    assert not ok and result["correct"] is False
    assert any(l.startswith(f"compare {number}") and "NOT CORRECT" in l
               for l in out.splitlines()), out
