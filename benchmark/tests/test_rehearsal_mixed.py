"""The cell PR 41 brings, end to end at a tiny size without the chip:
``dense_tree_mixed_push_pull`` (a gradient tree handed over in one call in
the job's bfloat16, over an f32 store under ``lamb``), through the harness's
own functions on four virtual CPU devices and on one.
``cells/tiny-lamb-bf16.json`` is ``cells/tiny-lamb.json`` with the job's
dtype and the limit of the pulled values; ``cells/tiny-tree-bf16.json`` is a
tiny twin of ``traffic/device-tree-bf16.json``.
"""

import inspect
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import harness
import tiny
from conftest import BENCH, HERE, ROOT

tiny.KINDS["lamb-bf16"] = ("tiny-lamb-bf16.json", "tiny-tree-bf16.json")
CELL = "bert-large-lamb-bf16.tree"
NEW = {"mixed_update_ms", "mixed_update_roofline", "convert_ms"}


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _run(seed=7, seconds=0.3, trace=False, **kw):
    return harness.run_cell(tiny.cell("lamb-bf16"), seed, seconds, trace,
                            time.perf_counter(), require_tpu=False, **kw)


def _value(out, line):
    text = next(l for l in out.splitlines() if l.startswith(line + ":"))
    return float(text.split()[2]), text


def test_the_tiny_files_are_the_cells_own_but_for_size():
    small = _json(HERE, "cells", "tiny-tree-bf16.json")
    full = _json(BENCH, "traffic", "device-tree-bf16.json")
    assert small["driver"] == full["driver"] == "dense_tree_mixed_push_pull"
    assert set(small) - {"name"} <= set(full)
    assert small["drawn_sampled"] == full["drawn_sampled"]
    tiny_cfg = _json(HERE, "cells", "tiny-lamb-bf16.json")
    cfg = _json(BENCH, "configs", "bert-large-lamb-bf16.json")
    for key in ("server_handle", "no_decay_no_adapt", "dtype", "job_dtype",
                "kind"):
        assert tiny_cfg[key] == cfg[key]
    assert tiny_cfg["limits"]["pulled_err"] == cfg["limits"]["pulled_err"]
    plain = _json(HERE, "cells", "tiny-lamb.json")
    assert tiny_cfg["tensors"] == plain["tensors"]


def test_the_configuration_is_the_f32_siblings_but_for_the_jobs_dtype():
    cfg = _json(BENCH, "configs", "bert-large-lamb-bf16.json")
    f32 = _json(BENCH, "configs", "bert-large-lamb.json")
    for key in ("tensors", "sizes", "parameters", "server_handle",
                "no_decay_no_adapt", "dtype", "chips", "kind", "reduced"):
        assert cfg[key] == f32[key], key
    assert (cfg["dtype"], cfg["job_dtype"]) == ("float32", "bfloat16")
    assert "job_dtype" not in f32
    assert cfg["reduced"] == [] and cfg["parameters"] == 336226108
    # Held to the sibling's own limits on the store, and one of its own.
    for name, limit in f32["limits"].items():
        assert cfg["limits"][name] == limit
    assert set(cfg["limits"]) - set(f32["limits"]) == {"pulled_err"}
    assert 2.0 ** -8 < cfg["limits"]["pulled_err"] < 2.0 ** -7
    assert set(cfg["limits"]) <= set(cfg["limits_why"])
    assert f32["guarantees"].replace(" No cell may weaken this.", "") \
        in cfg["guarantees"]
    for said in ("bf16 gradient widened exactly", "f32 on every shard",
                 "nearest-even", "16-bit master"):
        assert said in cfg["guarantees"], said
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bert-large-lamb-bf16", "device-tree-bf16", 1)
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
        elif m["name"].startswith("lamb_"):
            assert CELL not in m["workloads"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # The traffic is the sibling's but for the driver and the words.
    ours = _json(BENCH, "traffic", "device-tree-bf16.json")
    theirs = _json(BENCH, "traffic", "device-tree.json")
    for key in set(theirs) - {"name", "driver", "what"}:
        assert ours[key] == theirs[key], key


def test_cell_end_to_end_on_four_devices(capsys):
    ok, result = _run(seed=2**31 + 9)
    out = capsys.readouterr().out
    assert ok and result["correct"] and result["failed"] == 0
    tiny.check_metrics(result, "end_to_end", {"goodput", "step_p50",
                                              "step_p95", "setup_s"})
    assert result["device"]["count"] == 4 and result["attempted"] >= 1
    assert "0 compilations in the window" in out
    # The job's bytes: 2 B pushed and 2 B pulled a parameter.
    assert f"{4 * 214598:,} payload bytes a step" in out
    for name in ("engine_byte_counters_gap", "pulled_not_rounded_store",
                 "lamb_step_slot_gap", "nonfinite_in_sampled_stores",
                 "shards_not_1_over_W",
                 "store_or_moments_not_f32_or_pulled_not_job_dtype"):
        assert f"compare {name}: 0.0" in out, name
    first3, _ = _value(out, "compare first3_err")
    final, _ = _value(out, "compare final_err")
    pulled, _ = _value(out, "compare pulled_err")
    assert first3 < 1e-5 and final < 1e-4
    # Half a bfloat16 step: never over 2^-8 (+ the store's own error), and
    # some value of tens of thousands comes near it.
    assert 3.5e-3 < pulled < 2.0 ** -8 + 1e-5


def test_traced_run_on_a_cpu_reads_no_device_metric():
    """The three new readers are asked (a tiny cell carries the whole of
    ``per_layer``) and return nothing without a device plane."""
    ok, result = _run(trace=True, seconds=4.0)
    assert ok
    got = tiny.check_metrics(result, "per_layer",
                             {"issue_ms", "wait_ms", "compiles_in_window"})
    assert not got & (NEW | {"lamb_update_ms", "busy_ms", "roofline_share"})
    if "ops_per_step" in result["metrics"]:
        assert abs(result["metrics"]["ops_per_step"]["value"] - 1.0) < 0.05


@pytest.mark.parametrize("kind", ["dense", "lamb"])
def test_the_new_readers_read_nothing_in_the_cells_that_were_there(kind):
    """A tiny cell carries every reader: in a cell whose configuration has
    one dtype the new ones find nothing to read and do not raise."""
    import test_rehearsal_lamb  # noqa: F401  (registers the lamb kind)

    ok, result = harness.run_cell(tiny.cell(kind), 3, 2.0, True,
                                  time.perf_counter(), require_tpu=False)
    assert ok and not set(result["metrics"]) & NEW


def test_one_call_and_one_wait_a_step_through_kvworker(monkeypatch):
    """No side door: every step is one ``KVWorker.push_pull`` of all keys
    with bfloat16 rows ``[W, parameters]``, routed to the engine, counted as narrow."""
    from pslite_tpu import KVWorker
    from pslite_tpu.parallel.engine import CollectiveEngine

    cls = harness.load_driver(tiny.cell("lamb-bf16").search,
                              "dense_tree_mixed_push_pull")
    with open(inspect.getsourcefile(cls.checked_steps)) as fh:
        text = fh.read()
    assert "eng.push" not in text and "engine.push" not in text
    assert "eng._" not in text and "engine._" not in text
    seen = {"kv": [], "wait": 0, "engines": set()}
    kv_pp, kv_wait, eng_pp = (KVWorker.push_pull, KVWorker.wait,
                              CollectiveEngine.push_pull)

    def push_pull(self, keys, vals, outs, lens=None, **kw):
        seen["kv"].append((len(keys), lens, outs, str(vals.dtype),
                           vals.shape))
        return kv_pp(self, keys, vals, outs, lens, **kw)

    def wait(self, ts, *a, **kw):
        seen["wait"] += 1
        return kv_wait(self, ts, *a, **kw)

    def eng_push_pull(self, name, grads, *a, **kw):
        seen["engines"].add(self)
        return eng_pp(self, name, grads, *a, **kw)

    monkeypatch.setattr(KVWorker, "push_pull", push_pull)
    monkeypatch.setattr(KVWorker, "wait", wait)
    monkeypatch.setattr(CollectiveEngine, "push_pull", eng_push_pull)
    ok, result = _run(seed=3)
    assert ok
    steps = len(seen["kv"])
    assert steps >= 5 and seen["wait"] == steps
    assert set(seen["kv"]) == {(16, None, None, "bfloat16", (4, 214598))}
    (eng,) = seen["engines"]
    assert eng.lamb_updates == eng.narrow_ops == steps
    bucket = eng.bucket("tree")
    assert bucket.mixed and str(bucket.job_dtype) == "bfloat16"


def test_same_seed_same_inputs_and_the_bf16_master_fails_the_store(capsys):
    _run(seed=11, control="bf16")
    first = capsys.readouterr().out
    _run(seed=11)
    second = capsys.readouterr().out
    pick = lambda text: [l for l in text.splitlines()
                         if l.startswith("compare first3_err")]
    assert pick(first) == pick(second)
    for number in ("first3_err", "final_err", "pulled_err"):
        value, line = _value(first, f"control[bf16] {number}")
        assert "fails, as it must" in line
        sound, _ = _value(first, f"compare {number}")
        # By an order of magnitude and more, each of them.
        assert value > (30 if number != "pulled_err" else 3) * sound


def test_one_device_in_a_child_process():
    code = (
        "import sys, time\n"
        f"sys.path[:0] = [{ROOT!r}, {BENCH!r}, {HERE!r}]\n"
        "from pslite_tpu.utils.platform_pin import pin_cpu\n"
        "pin_cpu(1)\n"
        "import harness, tiny\n"
        "tiny.KINDS['lamb-bf16'] = ('tiny-lamb-bf16.json',\n"
        "                           'tiny-tree-bf16.json')\n"
        "ok, r = harness.run_cell(tiny.cell('lamb-bf16', chips=1), 5, 0.3,\n"
        "                         False, time.perf_counter(),\n"
        "                         require_tpu=False)\n"
        "assert ok and r['correct'] and r['device']['count'] == 1, r\n"
        "print('ONE-DEVICE-OK')\n")
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false",
               PS_CHECK_FATAL="0")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert "ONE-DEVICE-OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]


# -- programs that are wrong in the ways mixed precision can be ----------------


def _break_truncate_the_pulled_tree(monkeypatch):
    """Rounding toward zero on the way out: the bits below bfloat16's are
    cut off, in XLA's narrowing (several shards)."""
    import jax.numpy as jnp
    from jax import lax

    from pslite_tpu.parallel import engine

    def truncated(store_l, dtype):
        bits = lax.bitcast_convert_type(store_l, jnp.uint32)
        return lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), jnp.float32).astype(dtype)

    monkeypatch.setattr(engine, "_narrowed", truncated)


def _break_keep_a_bf16_master(monkeypatch):
    """The store rounded to bfloat16 after every step: a 16-bit master in
    f32 clothes.  The pulled values are still the store's rounding."""
    import jax.numpy as jnp

    from pslite_tpu.parallel import engine

    real = engine.CollectiveEngine._lamb_fn

    def lamb_fn(self, handle, bucket):
        inner = real(self, handle, bucket)

        def fn(store_l, state_l, agg, **kw):
            new_store, *rest = inner(store_l, state_l, agg, **kw)
            return (new_store.astype(jnp.bfloat16).astype(jnp.float32),
                    *rest)

        return fn

    monkeypatch.setattr(engine.CollectiveEngine, "_lamb_fn", lamb_fn)


def _break_sum_the_workers_in_bf16(monkeypatch):
    """The gradients added in the job's dtype and widened after."""
    from pslite_tpu.parallel import engine

    def widened(rows_l, dtype, width):
        return rows_l                    # stays bfloat16 through the sum

    monkeypatch.setattr(engine, "_widened", widened)


@pytest.mark.parametrize("breaker, fails", [
    (_break_truncate_the_pulled_tree, "pulled_not_rounded_store"),
    (_break_keep_a_bf16_master, "first3_err"),
    (_break_sum_the_workers_in_bf16, "first3_err"),
])
def test_a_broken_mixed_program_is_not_correct(breaker, fails, monkeypatch,
                                               capsys):
    breaker(monkeypatch)
    ok, result = _run(seed=5)
    out = capsys.readouterr().out
    assert not ok and result["correct"] is False
    assert any(l.startswith(f"compare {fails}") and "NOT CORRECT" in l
               for l in out.splitlines()), out
