"""The driver PR 27 brings, end to end at a tiny size without the chip:
``sparse_handle_pull_push`` (an embedding table under the stateful server
handle, every push through ``KVWorker.push_sparse``), through the
harness's own functions on four virtual CPU devices.  Its traffic file
here is a tiny twin of ``traffic/zipf-rows-handle.json``; the
configuration is the rehearsal's.
"""

import inspect
import json
import os
import subprocess
import sys
import time

import pytest

import harness
from conftest import BENCH, HERE, ROOT
from tiny import check_metrics
from tiny_handle import cell as _cell



def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _run(kind, seed=7, seconds=0.3, trace=False, **kw):
    return harness.run_cell(_cell(kind), seed, seconds, trace,
                            time.perf_counter(), require_tpu=False, **kw)


def test_the_tiny_traffic_file_is_the_cells_own_but_for_size():
    small = _json(HERE, "cells", "tiny-zipf-handle.json")
    full = _json(BENCH, "traffic", "zipf-rows-handle.json")
    assert small["driver"] == full["driver"]
    assert set(small) - {"name"} <= set(full)


def test_cell_end_to_end_on_four_devices(capsys):
    ok, result = _run("handle", seed=2**31 + 9)
    out = capsys.readouterr().out
    assert ok and result["correct"] and result["failed"] == 0
    check_metrics(result, "end_to_end", {"goodput", "step_p50", "step_p95",
                                         "setup_s"})
    assert result["device"]["count"] == 4 and result["attempted"] >= 1
    assert "0 compilations in the window" in out
    assert "compare first3_err" in out and "compare final_err" in out
    assert "compare engine_byte_counters_gap: 0.0" in out
    # the sum cell's three exact checks stay
    for name in ("hot_row_copies_spread", "hot_row_copies_missing",
                 "nonfinite_in_pulled_rows"):
        assert f"compare {name}: 0.0" in out


def test_traced_run_on_a_cpu_reads_no_device_metric():
    """``combine_ms`` and ``table_write_ms`` are asked of every tiny cell
    (it carries the whole of ``per_layer``) and return nothing without a
    device plane."""
    ok, result = _run("handle", trace=True)
    assert ok
    check_metrics(result, "per_layer", {"issue_ms", "wait_ms",
                                        "compiles_in_window"})


def test_the_bf16_control_fails_both_numbers(capsys):
    _run("handle", seed=11, control="bf16")
    out = capsys.readouterr().out
    for number in ("first3_err", "final_err"):
        line = next(l for l in out.splitlines()
                    if l.startswith(f"control[bf16] {number}"))
        assert "fails, as it must" in line
        sound = next(l for l in out.splitlines()
                     if l.startswith(f"compare {number}"))
        assert float(line.split()[2]) > 30 * float(sound.split()[2])


def test_every_push_goes_through_kvworker_under_the_handle(monkeypatch):
    """No side door: the driver's text never reaches for the engine's
    ``push``, and every push the engine sees came down from
    ``KVWorker.push_sparse`` with the configuration's handle."""
    from pslite_tpu import KVWorker
    from pslite_tpu.parallel.sparse import SparseEngine

    cls = harness.load_driver(_cell("handle").search,
                              "sparse_handle_pull_push")
    base = harness.load_driver(_cell("handle").search, "sparse_pull_push")
    assert issubclass(cls, base)
    assert {k for k in vars(cls) if not k.startswith("__")} \
        == {"step", "compare", "least_bytes"}
    with open(inspect.getsourcefile(cls.step)) as fh:
        text = fh.read()
    # ``r.push`` is the reference's; nothing else in the file pushes.
    assert "sparse.push" not in text
    assert ".push(" not in text.replace("r.push(", "")
    seen = {"kv": [], "engine": []}
    kv_push, eng_push = KVWorker.push_sparse, SparseEngine.push

    def push_sparse(self, name, indices, grads, handle=None, callback=None):
        seen["kv"].append(handle)
        return kv_push(self, name, indices, grads, handle, callback)

    def push(self, name, indices, grads, handle=None):
        seen["engine"].append(handle)
        return eng_push(self, name, indices, grads, handle)

    monkeypatch.setattr(KVWorker, "push_sparse", push_sparse)
    monkeypatch.setattr(SparseEngine, "push", push)
    ok, result = _run("handle", seed=3)
    assert ok
    handle = _cell("handle").config["server_handle"]
    assert seen["kv"] and seen["kv"] == seen["engine"]
    assert set(seen["kv"]) == {handle}


def _lose_every_fifth_push(monkeypatch):
    from pslite_tpu.parallel.sparse import SparseEngine

    real = SparseEngine.push
    calls = []

    def push(self, name, indices, grads, *a, **kw):
        calls.append(1)
        if len(calls) % 5 == 0:
            return self._stores[name][:1, :1]
        return real(self, name, indices, grads, *a, **kw)

    monkeypatch.setattr(SparseEngine, "push", push)


def _swap_two_pushes(monkeypatch):
    """Pushes 7 and 8 are applied in the other order: each row still gets
    every gradient once, only the order issued is broken."""
    from pslite_tpu.parallel.sparse import SparseEngine

    real = SparseEngine.push
    calls, held = [], []

    def push(self, name, indices, grads, *a, **kw):
        calls.append(1)
        if len(calls) == 7:
            held.append((indices, grads))
            return self._stores[name][:1, :1]
        token = real(self, name, indices, grads, *a, **kw)
        if len(calls) == 8:
            token = real(self, name, *held.pop(), *a, **kw)
        return token

    monkeypatch.setattr(SparseEngine, "push", push)


@pytest.mark.parametrize("breaker", [_lose_every_fifth_push,
                                     _swap_two_pushes])
def test_a_broken_timed_path_is_not_correct(breaker, monkeypatch, capsys):
    breaker(monkeypatch)
    ok, result = _run("handle", seed=5, seconds=0.5)
    out = capsys.readouterr().out
    assert not ok and result["correct"] is False
    assert any(l.startswith("compare final_err") and "NOT CORRECT" in l
               for l in out.splitlines()), out


def test_the_handle_cell_on_one_device_in_a_child_process():
    code = (
        "import json, os, sys, time\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'\n"
        "os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'\n"
        f"sys.path[:0] = [{BENCH!r}, {ROOT!r}, {HERE!r}]\n"
        "import harness, tiny_handle as t\n"
        "ok, r = harness.run_cell(t.cell('handle', chips=1), 3, 0.2, False,"
        " time.perf_counter(), require_tpu=False)\n"
        "assert ok and r['device']['count'] == 1, r\n"
        "print('ONE_DEVICE_OK')\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "PS_LOOPBACK_NS"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert "ONE_DEVICE_OK" in out.stdout, out.stderr[-3000:]
