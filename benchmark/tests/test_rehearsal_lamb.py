"""The cell PR 33 brings, end to end at a tiny size without the chip:
``dense_tree_push_pull`` (a gradient tree handed over in one call of keys
with their own lengths, under ``lamb``), through the harness's own functions
on four virtual CPU devices and on one.  ``cells/tiny-lamb.json`` has
``bert-large-lamb``'s handle, exclusion rule and kinds of keys (one across
shard borders, one on no lane border, one of two values, and an adapted key
last, so that counted padding shows); ``cells/tiny-tree.json`` is a tiny twin
of ``traffic/device-tree.json``.
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

tiny.KINDS["lamb"] = ("tiny-lamb.json", "tiny-tree.json")
_cell = tiny.cell


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _run(seed=7, seconds=0.3, trace=False, **kw):
    return harness.run_cell(_cell("lamb"), seed, seconds, trace,
                            time.perf_counter(), require_tpu=False, **kw)


def test_the_tiny_files_are_the_cells_own_but_for_size():
    small = _json(HERE, "cells", "tiny-tree.json")
    full = _json(BENCH, "traffic", "device-tree.json")
    assert small["driver"] == full["driver"] == "dense_tree_push_pull"
    assert set(small) - {"name"} <= set(full)
    assert small["drawn_sampled"] == full["drawn_sampled"]
    tiny_cfg = _json(HERE, "cells", "tiny-lamb.json")
    cfg = _json(BENCH, "configs", "bert-large-lamb.json")
    for key in ("server_handle", "no_decay_no_adapt", "dtype", "kind"):
        assert tiny_cfg[key] == cfg[key]


def test_the_configuration_is_bert_large_adams_tree_under_lamb():
    cfg = _json(BENCH, "configs", "bert-large-lamb.json")
    adam = _json(BENCH, "configs", "bert-large-adam.json")
    assert cfg["tensors"] == adam["tensors"] and cfg["sizes"] == adam["sizes"]
    assert cfg["parameters"] == adam["parameters"] == 336226108
    assert cfg["reduced"] == [] and cfg["chips"] == 1
    assert cfg["server_handle"] == "lamb:1e-4,0.9,0.999,1e-6,0.01"
    cls = harness.load_driver(_cell("lamb").search, "dense_tree_push_pull")
    import buckets

    names = [n for n, _ in buckets.expand_tensors(cfg["tensors"])]
    assert len(names) == 398
    matches = cls.step.__globals__["_matches"]
    excluded = [n for n in names if matches(n, cfg["no_decay_no_adapt"])]
    # 2 a LayerNorm (50 of them), 1 a bias (24 x 6 + pooler, mlm.dense,
    # mlm.bias, nsp): every tensor of one dimension, and no other.
    shapes = dict((n, s) for n, s in _expanded_shapes(cfg["tensors"]))
    assert sorted(excluded) == sorted(n for n in names
                                      if len(shapes[n]) == 1)
    assert len(excluded) == 2 * 50 + 24 * 6 + 4
    traffic = _json(BENCH, "traffic", "device-tree.json")
    assert set(traffic["always_sampled"]) <= set(names)
    for pattern in traffic["drawn_sampled"]:
        assert any(matches(n, [pattern]) for n in names)


def _expanded_shapes(entries, prefix=""):
    for entry in entries:
        if isinstance(entry, dict):
            for i in range(entry["repeat"]):
                yield from _expanded_shapes(entry["tensors"],
                                            f"{prefix}{entry['name']}.{i}.")
        else:
            yield prefix + entry[0], entry[1]


def test_cell_end_to_end_on_four_devices(capsys):
    ok, result = _run(seed=2**31 + 9)
    out = capsys.readouterr().out
    assert ok and result["correct"] and result["failed"] == 0
    tiny.check_metrics(result, "end_to_end", {"goodput", "step_p50", "step_p95",
                                         "setup_s"})
    assert result["device"]["count"] == 4 and result["attempted"] >= 1
    assert "0 compilations in the window" in out
    assert "compare first3_err" in out and "compare final_err" in out
    for name in ("engine_byte_counters_gap", "lamb_step_slot_gap",
                 "nonfinite_in_sampled_stores", "shards_not_1_over_W"):
        assert f"compare {name}: 0.0" in out


def test_traced_run_on_a_cpu_reads_no_device_metric():
    """The three LAMB readers are asked (a tiny cell carries the whole of
    ``per_layer``) and return nothing without a device plane; the stage
    clock's are there, one op a step."""
    ok, result = _run(trace=True, seconds=4.0)
    assert ok
    got = tiny.check_metrics(result, "per_layer",
                             {"issue_ms", "wait_ms", "compiles_in_window"})
    assert not got & {"lamb_update_ms", "lamb_update_roofline",
                      "lamb_norm_ms", "busy_ms", "roofline_share"}
    if "ops_per_step" in result["metrics"]:
        assert abs(result["metrics"]["ops_per_step"]["value"] - 1.0) < 0.05
        assert "route_ms" in got


def test_one_call_and_one_wait_a_step_through_kvworker(monkeypatch):
    """No side door: every step is one ``KVWorker.push_pull`` of all keys
    with no ``lens`` of its own, routed to the engine, counted under LAMB."""
    from pslite_tpu import KVWorker
    from pslite_tpu.parallel.engine import CollectiveEngine

    cls = harness.load_driver(_cell("lamb").search, "dense_tree_push_pull")
    with open(inspect.getsourcefile(cls.step)) as fh:
        text = fh.read()
    assert "eng.push" not in text and "engine.push" not in text
    seen = {"kv": [], "wait": 0, "engines": set()}
    kv_pp, kv_wait, eng_pp = (KVWorker.push_pull, KVWorker.wait,
                              CollectiveEngine.push_pull)

    def push_pull(self, keys, vals, outs, lens=None, **kw):
        seen["kv"].append((len(keys), lens, outs))
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
    assert set(seen["kv"]) == {(16, None, None)}
    (eng,) = seen["engines"]
    assert eng.lamb_updates == steps
    assert eng.bucket("tree").lens is not None


def test_same_seed_same_inputs_and_the_bf16_control_fails_both(capsys):
    _run(seed=11, control="bf16")
    first = capsys.readouterr().out
    _run(seed=11)
    second = capsys.readouterr().out
    pick = lambda text: [l for l in text.splitlines()
                         if l.startswith("compare first3_err")]
    assert pick(first) == pick(second)
    for number in ("first3_err", "final_err"):
        line = next(l for l in first.splitlines()
                    if l.startswith(f"control[bf16] {number}"))
        assert "fails, as it must" in line
        sound = next(l for l in first.splitlines()
                     if l.startswith(f"compare {number}"))
        assert float(line.split()[2]) > 30 * float(sound.split()[2])


def test_one_device_in_a_child_process():
    code = (
        "import sys, time\n"
        f"sys.path[:0] = [{ROOT!r}, {BENCH!r}, {HERE!r}]\n"
        "from pslite_tpu.utils.platform_pin import pin_cpu\n"
        "pin_cpu(1)\n"
        "import harness, tiny\n"
        "tiny.KINDS['lamb'] = ('tiny-lamb.json', 'tiny-tree.json')\n"
        "ok, r = harness.run_cell(tiny.cell('lamb', chips=1), 5, 0.3,\n"
        "                         False, time.perf_counter(),\n"
        "                         require_tpu=False)\n"
        "assert ok and r['correct'] and r['device']['count'] == 1, r\n"
        "print('ONE-DEVICE-OK')\n")
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false",
               PS_CHECK_FATAL="0")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert "ONE-DEVICE-OK" in out.stdout, out.stdout[-2000:] + out.stderr[-3000:]


# -- programs that are wrong in the ways LAMB can be ---------------------------


def _break_skip_the_ratio(monkeypatch):
    """Plain Adam with decay: every key's trust ratio is 1."""
    import jax.numpy as jnp

    from pslite_tpu.parallel import engine

    monkeypatch.setattr(engine, "_lamb_ratios",
                        lambda sq, adapt: jnp.ones(sq.shape[0], jnp.float32))


def _break_norm_over_the_bucket(monkeypatch):
    """The norms are taken over all keys at once, as a handle that knows
    no borders would."""
    import jax.numpy as jnp

    from pslite_tpu.parallel import engine

    real = engine._lamb_ratios
    monkeypatch.setattr(
        engine, "_lamb_ratios",
        lambda sq, adapt: real(jnp.broadcast_to(sq.sum(axis=0), sq.shape),
                               adapt))


def _break_count_the_padding(monkeypatch):
    """The last key is taken to reach the end of the padded bucket, and
    behind the gradient's end the row holds something else than the zeros
    the program fills it with: that enters the key's ``u``."""
    import jax.numpy as jnp
    from jax import lax

    from pslite_tpu.parallel import engine

    real = engine.CollectiveEngine._lamb_fn

    def lamb_fn(self, handle, bucket):
        bucket.starts = bucket.starts.copy()
        bucket.starts[-1] = bucket.padded_len
        inner = real(self, handle, bucket)

        def fn(store_l, state_l, row):
            at = (lax.axis_index(self.axis) * store_l.shape[0]
                  + jnp.arange(row.shape[1]))
            return inner(store_l, state_l,
                         jnp.where(at >= bucket.total_len, 1.0, row))

        return fn

    monkeypatch.setattr(engine.CollectiveEngine, "_lamb_fn", lamb_fn)


@pytest.mark.parametrize("breaker", [_break_skip_the_ratio,
                                     _break_norm_over_the_bucket,
                                     _break_count_the_padding])
def test_a_broken_lamb_fails_first3_err(breaker, monkeypatch, capsys):
    breaker(monkeypatch)
    ok, result = _run(seed=5)
    out = capsys.readouterr().out
    assert not ok and result["correct"] is False
    assert any(l.startswith("compare first3_err") and "NOT CORRECT" in l
               for l in out.splitlines()), out
