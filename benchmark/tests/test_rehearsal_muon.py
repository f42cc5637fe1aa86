"""The cell PR 43 brings, end to end at a tiny size without the chip:
``dense_tree_muon_push_pull`` (a gradient tree handed over in one call of
keys with their own lengths and shapes, under ``muon``), through the
harness's own functions.  The handle runs where one shard holds the bucket,
so every run here is a child process on ONE virtual CPU device (this
process has four).  ``cells/tiny-muon.json`` has ``moonlight-16b-muon``'s
handle, AdamW rule and kinds of keys (wide, tall and square matrices, a
router, a gain of 33 values on no lane border, keys of one shape that share
a batched product); ``cells/tiny-tree-muon.json`` is a tiny twin of
``traffic/device-tree-muon.json``.  The limits of the tiny cell are set as
the full cell's are: between the sound reading and the controls'.
"""

import json
import os
import subprocess
import sys

import pytest

import harness
import muon_flops
from conftest import BENCH, HERE, ROOT

PRELUDE = (
    "import sys, time, json\n"
    f"sys.path[:0] = [{ROOT!r}, {BENCH!r}, {HERE!r}]\n"
    "from pslite_tpu.utils.platform_pin import pin_cpu\n"
    "pin_cpu({devices})\n"
    "import harness, tiny\n"
    "tiny.KINDS['muon'] = ('tiny-muon.json', 'tiny-tree-muon.json')\n"
    "def run(seed=5, seconds=0.3, trace=False, chips=1, **kw):\n"
    "    return harness.run_cell(tiny.cell('muon', chips=chips), seed,\n"
    "                            seconds, trace, time.perf_counter(),\n"
    "                            require_tpu=False, **kw)\n")


def _child(body: str, devices: int = 1, timeout: int = 600):
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false",
               PS_CHECK_FATAL="0")
    out = subprocess.run(
        [sys.executable, "-c", PRELUDE.format(devices=devices) + body],
        capture_output=True, text=True, env=env, timeout=timeout)
    return out.stdout, out.stderr


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def test_the_tiny_files_are_the_cells_own_but_for_size():
    small = _json(HERE, "cells", "tiny-tree-muon.json")
    full = _json(BENCH, "traffic", "device-tree-muon.json")
    assert small["driver"] == full["driver"] == "dense_tree_muon_push_pull"
    assert set(small) - {"name"} <= set(full)
    for key in ("always_sampled", "drawn_sampled", "first_step_sampled",
                "sampled_keys", "error_floor"):
        assert small[key] == full[key]
    tiny_cfg = _json(HERE, "cells", "tiny-muon.json")
    cfg = _json(BENCH, "configs", "moonlight-16b-muon.json")
    for key in ("server_handle", "adamw_keys", "dtype", "kind", "chips"):
        assert tiny_cfg[key] == cfg[key]
    assert set(tiny_cfg["limits"]) == set(cfg["limits"]) == {
        "first3_err", "first3_rms", "final_err", "final_rms"}
    # Every pattern the traffic samples by names a key of both trees, and
    # one key of every shape class is among them.
    for tree in (tiny_cfg, cfg):
        names = [n for n, _ in muon_flops.expand_shapes(tree["tensors"])]
        for name in full["always_sampled"] + full["first_step_sampled"]:
            assert name in names
        for pattern in full["drawn_sampled"]:
            assert any(muon_flops.is_adamw(n, [pattern]) for n in names)
    shapes = dict(muon_flops.expand_shapes(cfg["tensors"]))
    assert shapes["moe.0.expert.0.gate.w"] == (1408, 2048)      # wide
    assert shapes["moe.3.expert.7.down.w"] == (2048, 1408)      # tall
    assert shapes["dense.0.attn.kv_a.w"] == (576, 2048)
    assert shapes["moe.1.attn.kv_b.w"] == (4096, 512)
    assert shapes["moe.2.router.w"] == (64, 2048)
    assert shapes["moe.0.attn.o.w"] == (2048, 2048)             # square
    assert shapes["dense.0.mlp.down.w"] == (2048, 11264)
    assert 576 * 2048 == full["followed_key_elements"]


def test_a_sound_run_passes_and_both_controls_fail():
    """One run with the controls read beside it (``readings.py``'s path):
    one ``KVWorker.push_pull`` of all 53 keys and one ``wait`` a step, the
    engine counting every op under Muon; ``correct``, the exact numbers 0;
    each control fails at least one number, by far."""
    out, err = _child(
        "from pslite_tpu import KVWorker\n"
        "seen = []\n"
        "real = KVWorker.push_pull\n"
        "def push_pull(self, keys, vals, outs, lens=None, **kw):\n"
        "    seen.append((len(keys), lens, outs))\n"
        "    return real(self, keys, vals, outs, lens, **kw)\n"
        "KVWorker.push_pull = push_pull\n"
        "ok, r = run(seed=2**31 + 9, control='bf16')\n"
        "print('RESULT', json.dumps(r))\n"
        "print('CALLS', len(seen), sorted(set(seen)))\n")
    assert "RESULT" in out, out[-2000:] + err[-3000:]
    result = json.loads(next(l for l in out.splitlines()
                             if l.startswith("RESULT"))[7:])
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 1 and result["attempted"] >= 1
    assert {"goodput", "step_p50", "step_p95", "setup_s"} <= set(
        result["metrics"])
    assert "0 compilations in the window" in out
    for name in ("engine_byte_counters_gap", "muon_step_slot_gap",
                 "nonfinite_in_sampled_stores",
                 "state_bytes_over_4_a_muon_8_an_adamw_value",
                 "shards_not_1_over_W"):
        assert f"compare {name}: 0.0" in out, name
    calls = next(l for l in out.splitlines() if l.startswith("CALLS"))
    steps = int(calls.split()[1])
    assert steps == result["attempted"] + 3 + 1     # checked and warm steps
    assert calls.endswith("[(53, None, None)]")
    sound = {l.split()[1].rstrip(":"): float(l.split()[2])
             for l in out.splitlines() if l.startswith("compare ")}
    for control in ("stored values rounded", "4 newton-schulz steps"):
        lines = [l for l in out.splitlines()
                 if l.startswith("control[bf16]") and f"[{control}]" in l]
        assert len(lines) == 4
        assert any("fails, as it must" in l for l in lines), lines
        for l in lines:
            number = l.split()[1].split("[")[0]
            value = float(l.split(": ")[1].split()[0])
            assert value > 15 * sound[number], l


BREAKERS = {
    "four_steps": (
        "import functools\n"
        "from pslite_tpu.ops import muon\n"
        "muon.newton_schulz = functools.partial(muon.newton_schulz, steps=4)\n"),
    "no_nesterov": (
        "import inspect\n"
        "from pslite_tpu.ops import muon\n"
        "code = inspect.getsource(muon.muon_update).replace("
        "'x = (g + mu * mom).astype(bf16)', 'x = mom.astype(bf16)')\n"
        "assert 'x = mom.astype(bf16)' in code\n"
        "exec(code, muon.__dict__)\n"),
    "adamw_for_a_matrix": (
        "from pslite_tpu.parallel import engine\n"
        "real = engine.CollectiveEngine._muon_plan\n"
        "from pslite_tpu.ops.muon import muon_plan\n"
        "def plan(self, bucket):\n"
        "    if bucket.muon_plan is None:\n"
        "        ew = (bucket.flags & engine.KEY_ELEMENTWISE) != 0\n"
        "        ew[-3] = True      # the last expert's down.w, silently\n"
        "        bucket.muon_plan = muon_plan(bucket.shapes, ew)\n"
        "    return bucket.muon_plan\n"
        "engine.CollectiveEngine._muon_plan = plan\n"),
    "a_tall_key_not_transposed_back": (
        "from pslite_tpu.ops import muon\n"
        "import inspect\n"
        "code = inspect.getsource(muon.muon_update).replace("
        "'(o[i].T if tall else o[i])', 'o[i]')\n"
        "assert '= o[i].reshape' in code\n"
        "exec(code, muon.__dict__)\n"),
}


@pytest.mark.parametrize("breaker", sorted(BREAKERS))
def test_a_broken_muon_comes_out_not_correct(breaker):
    """Programs that are wrong in the ways Muon can be: a Newton-Schulz
    step left out, the Nesterov term dropped, a matrix quietly handed to
    AdamW (the silent fall-back the handle refuses), a tall key's update
    written untransposed."""
    out, err = _child(BREAKERS[breaker]
                      + "ok, r = run(seed=5)\nprint('RESULT', json.dumps(r))\n")
    assert "RESULT" in out, out[-2000:] + err[-3000:]
    result = json.loads(next(l for l in out.splitlines()
                             if l.startswith("RESULT"))[7:])
    assert result["correct"] is False
    # A key that is not sampled shows in no error; the state's bytes tell
    # of it (8 B a value where a matrix has 4).
    caught = ("compare state_bytes_over" if breaker == "adamw_for_a_matrix"
              else "compare first3_")
    assert any(l.startswith(caught) and "NOT CORRECT" in l
               for l in out.splitlines()), out[-3000:]


def test_on_four_devices_the_cell_is_refused_by_name():
    out, err = _child(
        "try:\n"
        "    run(chips=4)\n"
        "except Exception as exc:\n"
        "    print('REFUSED', type(exc).__name__, exc)\n", devices=4)
    line = next((l for l in out.splitlines() if l.startswith("REFUSED")),
                None)
    assert line is not None, out[-2000:] + err[-3000:]
    assert "works on whole matrices" in line and "4 shards" in line


def test_a_checkout_without_the_handle_fails_at_once_and_says_why(tmp_path):
    """What the driver does on the parent commit: the driver's import of
    the engine's new names raises before anything boots."""
    code = (
        "import sys\n"
        f"sys.path[:0] = [{ROOT!r}, {BENCH!r}]\n"
        "from pslite_tpu.parallel import engine\n"
        "del engine.KEY_ELEMENTWISE\n"
        "import harness\n"
        "try:\n"
        "    harness.resolve(harness.load_cell('moonlight-16b-muon.tree'))\n"
        "except RuntimeError as exc:\n"
        "    print('REFUSED', exc)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "REFUSED this checkout's engine keeps no per-key shapes" \
        in out.stdout, out.stdout[-1000:] + out.stderr[-2000:]
    assert "muon" in out.stdout
