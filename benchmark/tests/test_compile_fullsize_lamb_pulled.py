"""Compile-only: where the pulled tree of ``bert-large-lamb.tree`` comes
from, at full size, for the v5e.  On one chip (the cell) one shard holds the
whole bucket, and ``lamb_apply`` leaves the new parameters twice: in place
in the store and as a vector ``f32[336226108]`` of its own, which is the
program's pulled result as it stands (PR 38; before it the program cut the
store after the kernel, ``%slice.2``, a copy of 2.69 GB; as a row
``f32[1,336226108]`` the kernel's result lies in tiles of 128 where the
program's vector lies in tiles of 1,024, and the compiler puts a ``reduce``
between them that copies the tree again).  On four chips the pulled tree is the all-gather of the
shards cut at the tree's length, as it was.  A compile that passes says a
program LOWERS and FITS, never that it runs or how fast.  As in
``test_compile_fullsize_lamb.py``, the topology is described inside a
fixture: only one process at a time may load the TPU's library.
"""

import fnmatch
import json
import os
import re

import numpy as np
import pytest

import buckets
from conftest import BENCH

jax = pytest.importorskip("jax")

HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # environment, not code
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compiled(topo, chips, op="push_pull_st"):
    """(compiled program, lowered text, total, padded) of the cell's bucket
    under the configuration's handle over ``chips`` described chips."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import (KEY_NO_ADAPT, KEY_NO_DECAY,
                                            CollectiveEngine, DenseBucket,
                                            _padded_len)

    with open(os.path.join(BENCH, "configs", "bert-large-lamb.json")) as fh:
        config = json.load(fh)
    tensors = buckets.expand_tensors(config["tensors"])
    lens = np.array([n for _, n in tensors], dtype=np.int64)
    flags = np.array([
        (KEY_NO_DECAY | KEY_NO_ADAPT)
        if any(fnmatch.fnmatchcase(name, p)
               for p in config["no_decay_no_adapt"]) else 0
        for name, _ in tensors], dtype=np.int32)
    mesh = Mesh(np.array(topo.devices[:chips]), ("kv",))
    handle = config["server_handle"]
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    total = int(lens.sum())
    padded = _padded_len(total, chips, True)
    # The record alone: registering would allocate the store on a chip
    # that is described and not attached.
    bucket = DenseBucket(name="tree", keys=np.arange(398, dtype=np.uint64),
                         val_len=0, dtype=jnp.float32, total_len=total,
                         padded_len=padded, lens=lens, flags=flags)
    shard = NamedSharding(mesh, P("kv"))
    vec = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=shard)
    slot = jax.ShapeDtypeStruct((chips,), jnp.float32, sharding=shard)
    grads = jax.ShapeDtypeStruct(
        (chips, total), jnp.float32,
        sharding=NamedSharding(mesh, P("kv", None)))
    prog = eng._program(op, padded, jnp.float32, handle, bucket)
    lowered = prog.lower(vec, vec, vec, slot, grads)
    return lowered.compile(), lowered.as_text(), total, padded


def _makers(text, shape):
    """The opcodes of the operations of a compiled text whose first result
    is ``shape``, parameters apart."""
    found = re.findall(
        rf"^\s*(?:ROOT )?%[\w.\-]+ = \(?{re.escape(shape)}[{{,)\s]\S* "
        rf"([\w\-]+)\(", text, flags=re.M)
    return [opcode for opcode in found if opcode != "parameter"]


def test_on_one_chip_the_kernel_writes_the_pulled_tree(topo):
    compiled, lowered, total, padded = _compiled(topo, 1)
    assert (total, padded) == (336226108, 5131 * 65536)
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    rows = padded // 128
    assert f"%lamb_moments.1 = (f32[{rows},128]" in text
    # The store first, in place, under the kernel's name; the pulled
    # vector second.
    assert re.search(
        rf"%lamb_apply\.1 = \(f32\[{rows},128\]\S*, f32\[{total}\]",
        text)
    # Between the kernel and the program's pulled result nothing that moves
    # a value: no slice, copy, reduce or fusion makes 336,226,108 of them.
    made = _makers(text, f"f32[{total}]")
    assert made and set(made) <= {"get-tuple-element", "bitcast"}, made
    assert "all-gather" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * 4 * padded
    assert mem.temp_size_in_bytes < 10**7
    # Held as before: p, m, v, the gradient (5.38 GB) and the pulled tree
    # (1.345 GB), which is now the kernel's second result.
    args = 3 * 4 * padded + 4 * total + 4
    assert mem.argument_size_in_bytes - args < 10**4
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert args + 4 * total <= held < args + 4 * total + 10**7
    assert held < 0.5 * HBM


def test_on_four_chips_the_pulled_tree_is_the_gathered_shards(topo):
    compiled, lowered, total, padded = _compiled(topo, 4)
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    rows = padded // 4 // 128
    assert f"%lamb_apply.1 = f32[{rows},128]" in text
    assert "all-gather" in text and "all-reduce" in text
    assert set(_makers(text, f"f32[{total}]")) & {"slice", "fusion", "copy"}
    assert compiled.memory_analysis().alias_size_in_bytes >= 3 * 4 * padded // 4


def test_a_push_alone_has_the_one_result_kernel(topo):
    compiled, lowered, total, padded = _compiled(topo, 1, op="push_st")
    text = compiled.as_text()
    assert f"%lamb_apply.1 = f32[{padded // 128},128]" in text
    assert not _makers(text, f"f32[{total}]")
