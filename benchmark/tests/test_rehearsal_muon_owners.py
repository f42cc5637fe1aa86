"""The cell PR 56 brings, end to end at a tiny size without the chip:
``dense_tree_muon_owners_push_pull`` (four workers' rows of a gradient tree
in one call, under ``muon`` over four colocated servers, every matrix whole
on one owner), through the harness's own functions, in a child process on
FOUR virtual CPU devices.  ``cells/tiny-muon-4chip.json`` is
``cells/tiny-muon.json`` on four chips, as ``moonlight-16b-muon-4chip`` is
``moonlight-16b-muon``; ``cells/tiny-tree-muon-4workers.json`` is a tiny twin
of ``traffic/device-tree-muon-4workers.json`` (its ``state_padding`` is the
tiny tree's: a tree of 53 small keys leaves more of its classes over)."""

import json
import os
import subprocess
import sys

import pytest

import muon_flops
from conftest import BENCH, HERE, ROOT

PRELUDE = (
    "import sys, time, json\n"
    f"sys.path[:0] = [{ROOT!r}, {BENCH!r}, {HERE!r}]\n"
    "from pslite_tpu.utils.platform_pin import pin_cpu\n"
    "pin_cpu(4)\n"
    "import harness, tiny\n"
    "tiny.KINDS['muon4'] = ('tiny-muon-4chip.json',\n"
    "                       'tiny-tree-muon-4workers.json')\n"
    "def run(seed=5, seconds=0.3, trace=False, **kw):\n"
    "    return harness.run_cell(tiny.cell('muon4', chips=4), seed,\n"
    "                            seconds, trace, time.perf_counter(),\n"
    "                            require_tpu=False, **kw)\n")


def _child(body: str, timeout: int = 900):
    env = dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false",
               PS_CHECK_FATAL="0")
    out = subprocess.run([sys.executable, "-c", PRELUDE + body],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    return out.stdout, out.stderr


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def _result(out, err):
    assert "RESULT" in out, out[-2000:] + err[-3000:]
    return json.loads(next(l for l in out.splitlines()
                           if l.startswith("RESULT"))[7:])


def test_the_new_files_are_the_one_chip_cells_but_for_the_chips():
    cfg = _json(BENCH, "configs", "moonlight-16b-muon-4chip.json")
    one = _json(BENCH, "configs", "moonlight-16b-muon.json")
    assert cfg["chips"] == 4 and one["chips"] == 1
    # The same paper and shapes, down to the section that defines THIS
    # deployment.
    assert "Distributed Muon" in cfg["source"] != one["source"]
    assert cfg["source"].replace(" sec. 'Distributed Muon'", "") == \
        one["source"]
    for key in ("sizes", "published", "tensors", "adamw_keys",
                "server_handle", "reduced", "dtype", "parameters", "kind"):
        assert cfg[key] == one[key], key
    assert cfg["assumed"][:len(one["assumed"])] == one["assumed"]
    assert set(cfg["limits"]) == set(one["limits"])
    traffic = _json(BENCH, "traffic", "device-tree-muon-4workers.json")
    base = _json(BENCH, "traffic", "device-tree-muon.json")
    assert traffic["driver"] == "dense_tree_muon_owners_push_pull"
    for key in set(base) - {"name", "driver", "what"}:
        assert traffic[key] == base[key], key
    assert (traffic["owners_sampled"], traffic["state_padding"]) == (3, 0.1)
    small = _json(HERE, "cells", "tiny-tree-muon-4workers.json")
    assert small["driver"] == traffic["driver"]
    assert set(small) - {"name"} <= set(traffic)
    assert small["owners_sampled"] == traffic["owners_sampled"]
    tiny_cfg = _json(HERE, "cells", "tiny-muon-4chip.json")
    for key in ("server_handle", "adamw_keys", "dtype", "kind", "chips"):
        assert tiny_cfg[key] == cfg[key]
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "moonlight-16b-muon.tree.4chip")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "moonlight-16b-muon-4chip", "device-tree-muon-4workers", 4)
    names = [n for n, _ in muon_flops.expand_shapes(cfg["tensors"])]
    assert len(names) == 153


def test_a_sound_run_passes_and_both_controls_fail():
    """One run with the controls read beside it: one ``KVWorker.push_pull``
    of all 53 keys with four rows and one ``wait`` a step; ``correct``, the
    exact numbers 0; each control fails at least one number."""
    out, err = _child(
        "from pslite_tpu import KVWorker\n"
        "seen = []\n"
        "real = KVWorker.push_pull\n"
        "def push_pull(self, keys, vals, outs, lens=None, **kw):\n"
        "    seen.append((len(keys), tuple(vals.shape), lens, outs))\n"
        "    return real(self, keys, vals, outs, lens, **kw)\n"
        "KVWorker.push_pull = push_pull\n"
        "ok, r = run(seed=2**31 + 9, control='bf16')\n"
        "print('RESULT', json.dumps(r))\n"
        "print('CALLS', len(seen), sorted(set(seen)))\n")
    result = _result(out, err)
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4 and result["attempted"] >= 1
    assert {"goodput", "step_p50", "step_p95", "setup_s"} <= set(
        result["metrics"])
    assert "0 compilations in the window" in out
    for name in ("engine_byte_counters_gap", "muon_step_slot_gap",
                 "nonfinite_in_sampled_stores",
                 "state_bytes_over_4_a_muon_8_an_adamw_value_and_padding",
                 "shards_not_1_over_W",
                 "matrix_keys_across_a_border_or_keys_nowhere",
                 "workers_whose_pulled_tree_differs",
                 "sampled_keys_owners_short_of_the_traffics"):
        assert f"compare {name}: 0.0" in out, name
    calls = next(l for l in out.splitlines() if l.startswith("CALLS"))
    assert int(calls.split()[1]) == result["attempted"] + 3 + 1
    assert calls.endswith("[(53, (4, 585187), None, None)]")
    for control in ("stored values rounded", "4 newton-schulz steps"):
        lines = [l for l in out.splitlines()
                 if l.startswith("control[bf16]") and f"[{control}]" in l]
        assert len(lines) == 4
        assert any("fails, as it must" in l for l in lines), lines


def test_a_traced_run_reads_the_gauge_and_no_device_metric():
    out, err = _child("ok, r = run(seed=7, trace=True)\n"
                      "print('RESULT', json.dumps(r))\n")
    result = _result(out, err)
    assert result["correct"]
    metrics = result["metrics"]
    assert 1.0 <= metrics["muon_owner_flops_spread"]["value"] < 2.0
    assert metrics["launches_per_step"]["value"] == 1.0 \
        if "launches_per_step" in metrics else True
    for name in ("muon_owned_ns_ms", "muon_exchange_ms", "muon_place_ms",
                 "muon_owned_rest_ms", "muon_owned_rest_roofline"):
        assert name not in metrics      # a device trace's: none on a CPU


BREAKERS = {
    "a_worker_left_out": (
        "from pslite_tpu.parallel import engine\n"
        "real = engine._aggregate_whole\n"
        "def three(rows_l, *a, **kw):\n"
        "    from jax import lax\n"
        "    keep = (lax.axis_index('kv') != 3).astype(rows_l.dtype)\n"
        "    return real(rows_l * keep, *a, **kw)\n"
        "engine._aggregate_whole = three\n"),
    "an_owners_leftover_matrices_skipped": (
        "from pslite_tpu.ops import muon\n"
        "real = muon.owner_plan\n"
        "def plan(*a, **kw):\n"
        "    made = real(*a, **kw)\n"
        "    empty = tuple(() for _ in made.branches)\n"
        "    return made._replace(branches=empty)\n"
        "muon.owner_plan = plan\n"),
}


@pytest.mark.parametrize("breaker", sorted(BREAKERS))
def test_a_broken_exchange_or_deal_comes_out_not_correct(breaker):
    """Programs that are wrong in the ways the owners' layout can be: one
    worker's row left out of the sum; the matrices a shape class leaves
    over never updated by the owner that has them."""
    out, err = _child(BREAKERS[breaker] + "ok, r = run(seed=5)\n"
                      "print('RESULT', json.dumps(r))\n")
    result = _result(out, err)
    assert result["correct"] is False
    assert any(l.startswith("compare first3_") and "NOT CORRECT" in l
               for l in out.splitlines()), out[-3000:]
