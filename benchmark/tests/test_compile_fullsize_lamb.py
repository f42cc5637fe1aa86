"""Compile-only: the one program ``bert-large-lamb.tree`` runs, at full
size, for the v5e: one bucket of 398 keys and 336,226,108 values (321 times
the largest bucket any dense program here had seen), ``push_pull_st`` under
the configuration's ``lamb`` handle with its two Mosaic kernels, on one chip
(the cell) and on four (where the norms' ``psum`` crosses chips).  A compile
that passes says a program LOWERS and FITS, never that it runs or how fast.
As in ``test_compile_fullsize.py``, the topology is described inside a
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


@pytest.mark.parametrize("chips", [1, 4])
def test_the_tree_compiles_in_place_with_both_kernels(chips, topo):
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.ops.fused_update import LAMB_TILE
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
    assert len(lens) == 398 and int(lens.sum()) == config["parameters"]
    mesh = Mesh(np.array(topo.devices[:chips]), ("kv",))
    handle = config["server_handle"]
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    total = int(lens.sum())
    padded = _padded_len(total, chips, True)
    assert padded % (chips * LAMB_TILE) == 0 and padded - total < chips * LAMB_TILE
    # The record alone: registering would allocate the store on a chip
    # that is described and not attached.
    bucket = DenseBucket(name="tree", keys=np.arange(398, dtype=np.uint64),
                         val_len=0, dtype=jnp.float32, total_len=total,
                         padded_len=padded, lens=lens, flags=flags)
    shard = NamedSharding(mesh, P("kv"))
    vec = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=shard)
    slot = jax.ShapeDtypeStruct((chips,), jnp.float32, sharding=shard)
    # The gradient as the job has it: the keys' values and nothing behind
    # them (336,226,108 is a multiple of no tile, and not of 128).
    grads = jax.ShapeDtypeStruct(
        (chips, total), jnp.float32,
        sharding=NamedSharding(mesh, P("kv", None)))
    prog = eng._program("push_pull_st", padded, jnp.float32, handle, bucket)
    lowered = prog.lower(vec, vec, vec, slot, grads)
    assert lowered.as_text().count("tpu_custom_call") == 2
    compiled = lowered.compile()
    text = compiled.as_text()
    rows = padded // chips // 128
    assert f"%lamb_moments.1 = (f32[{rows},128]" in text
    assert f"%lamb_apply.1 = f32[{rows},128]" in text
    assert "f32[398,2]" in text and "f32[796]" in text
    if chips > 1:
        # The norms cross the chips (an all-reduce of two numbers a key),
        # as do the gradients and the pulled parameters.
        assert "all-reduce" in text and "all-gather" in text
    mem = compiled.memory_analysis()
    # p, m, v are donated and both kernels update in place: no copy of a
    # 1.3 GB vector to pad or reshape it, nothing kept for u.  On one chip
    # that holds for the gradient too, which the first kernel reads as a
    # row where it lies; over four the row is filled with zeros before the
    # reduce-scatter cuts it.
    assert mem.alias_size_in_bytes >= 3 * 4 * padded // chips
    assert f"f32[{total}]" in text          # the pulled tree, cut inside
    if chips == 1:
        assert mem.temp_size_in_bytes < 10**7
        assert not [l for l in text.splitlines() if " pad(" in l
                    and re.search(r"f32\[[\d,]*\d{7}", l.split(" pad(")[0])]
    # What one device holds while the program runs: its arguments, and the
    # pulled parameters beside them.
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < 0.5 * HBM
