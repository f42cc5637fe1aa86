"""Compile-only: the one program ``moonlight-16b-muon.tree`` runs, at full
size, for one v5e chip: one bucket of 153 keys and 568,484,352 values,
``push_pull_st`` under the configuration's ``muon`` handle: 135 matrices in
eleven batched chunks through five Newton-Schulz steps each, 18 keys under
AdamW, the store and the state at its own size donated and updated where
they lie.  A compile that passes says a program LOWERS and FITS, never that
it runs or how fast.  As in ``test_compile_fullsize.py``, the topology is
described inside a fixture: only one process at a time may load the TPU's
library.
"""

import json
import os
import re

import numpy as np
import pytest

import muon_flops
from conftest import BENCH

jax = pytest.importorskip("jax")

HBM = 16 * 10**9
_RESULT = re.compile(r"= \(?(\w+)\[([\d,]*)\]")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # environment, not code
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.ops import muon
    from pslite_tpu.parallel.engine import (KEY_ELEMENTWISE,
                                            CollectiveEngine, DenseBucket,
                                            _padded_len)

    with open(os.path.join(BENCH, "configs",
                           "moonlight-16b-muon.json")) as fh:
        config = json.load(fh)
    tensors = muon_flops.expand_shapes(config["tensors"])
    shapes = np.array([s for _, s in tensors], dtype=np.int64)
    lens = shapes[:, 0] * shapes[:, 1]
    flags = np.array([KEY_ELEMENTWISE
                      if muon_flops.is_adamw(n, config["adamw_keys"]) else 0
                      for n, _ in tensors], dtype=np.int32)
    total = int(lens.sum())
    assert len(lens) == 153 and total == config["parameters"]
    mesh = Mesh(np.array(topo.devices[:1]), ("kv",))
    handle = config["server_handle"]
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    padded = _padded_len(total, 1, True)
    # The record alone: registering would allocate the store on a chip
    # that is described and not attached.
    bucket = DenseBucket(name="tree", keys=np.arange(153, dtype=np.uint64),
                         val_len=0, dtype=jnp.float32, total_len=total,
                         padded_len=padded, lens=lens, flags=flags,
                         shapes=shapes)
    plan = eng._muon_plan(bucket)
    shard = NamedSharding(mesh, P("kv"))
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=shard)
    state = [sds(s) for s in muon.state_shapes(plan)] + [sds((1,))]
    grads = jax.ShapeDtypeStruct(
        (1, total), jnp.float32, sharding=NamedSharding(mesh, P("kv", None)))
    prog = eng._program("push_pull_st", padded, jnp.float32, handle, bucket)
    exe = prog.lower(sds((padded,)), *state, grads).compile()
    return exe, plan, total, padded


def test_the_plan_of_the_full_tree(compiled):
    _, plan, total, padded = compiled
    assert plan.matrices == 135 and len(plan.chunks) == 11
    assert plan.ns_flops == 20631616225280.0
    assert plan.muon_len + plan.adamw_len == total
    assert plan.state_bytes == 2609582080 < 3 * 4 * padded // 2


def test_store_and_state_are_donated_and_the_step_fits(compiled):
    exe, plan, total, padded = compiled
    mem = exe.memory_analysis()
    # The store and every array of the state are updated where they lie.
    assert mem.alias_size_in_bytes >= 4 * padded + plan.state_bytes
    # The bf16 temporaries of a chunk (X twice, A, B) and its gradient cut
    # into matrices, a chunk at a time: never the tree's.
    assert mem.temp_size_in_bytes < 1.5e9
    # What the device holds while the program runs: store, state and the
    # gradient (arguments), the pulled tree beside them, the temporaries;
    # and what the worker still keeps, the step before's pulled tree.
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held + 4 * total < 0.85 * HBM


def test_nothing_of_tree_size_but_the_updates_in_place_and_the_cut(compiled):
    """No ``copy``, ``pad``, ``convert`` or ``reduce`` of the tree's size
    anywhere; the one ``slice`` of that size is the pulled tree, cut from
    the new store inside the program (the all-gather of one shard is the
    identity); every other result of that size is the store itself, a
    ``dynamic-update-slice`` of one key in place."""
    exe, plan, total, padded = compiled
    text = exe.as_text()
    entry = text[text.index("ENTRY"):]
    big = []
    for line in entry.splitlines():
        m = _RESULT.search(line)
        if (not m or " parameter(" in line or "get-tuple-element(" in line
                or " tuple(" in line):
            continue
        n = int(np.prod([int(d) for d in m.group(2).split(",") if d] or [1],
                        dtype=np.int64))
        if n >= total:
            big.append(line.strip())
    kinds = {}
    for line in big:
        kind = re.search(r"\] ?(?:\{[^}]*\})? ([\w\-]+)\(", line)
        kinds.setdefault(kind.group(1) if kind else line[:60], []).append(
            line)
    print({k: len(v) for k, v in kinds.items()})
    allowed = {"dynamic-update-slice", "fusion", "slice", "bitcast",
               "opt-barrier"}
    assert set(kinds) <= allowed, sorted(set(kinds) - allowed)
    # One result of the pulled tree's size and no more: the cut.
    cuts = [l for l in big if f"f32[{total}]" in l]
    assert len(cuts) == 1, cuts
    # Every fusion of the store's size ends in a dynamic-update-slice.
    for line in kinds.get("fusion", []):
        if f"f32[{total}]" in line:
            continue
        assert "dynamic-update-slice" in line or "dynamic_update_slice" in \
            line, line[:200]


def test_the_products_are_bf16_with_f32_accumulation(compiled):
    exe, plan, total, padded = compiled
    text = exe.as_text()
    # 11 chunks x 5 steps x 3 products, each a convolution the MXU runs on
    # bf16 operands; none takes an f32 operand.
    products = [l for l in text.splitlines()
                if re.search(r"= \w+\[[\d,]+\]\S* convolution\(", l)]
    assert len(products) >= 11 * 15
    for line in products:
        assert "f32" not in re.findall(
            r"convolution\(([^)]*)\)", line)[0].replace("f32[]", ""), line[:200]
