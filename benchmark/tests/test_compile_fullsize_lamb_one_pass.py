"""Compile-only: the programs of ``bert-large-lamb.tree`` and
``bert-large-lamb-bf16.tree`` at full size, for the v5e, since LAMB takes
one pass over every key that VMEM holds (PR 42).  On one chip (the cells)
one shard holds the whole bucket: of the 398 keys all but ``emb.word``
(31,254,528 values) reach into at most 65 tiles, and ``lamb_apply`` (the
custom call of ``fused_update.lamb_one_pass``) reads g, m, v and p of
those once, holds their ``p`` and ``u`` in a ring of 65
tiles in VMEM and writes the store and the pulled vector 64 grid steps
behind what it reads; its results are the store, m and v in place and the
pulled vector of the job's dtype.  ``lamb_moments`` walks ``emb.word``'s
477 tiles alone.  No operation outside the two has a result of tree size.
A compile that passes says a program LOWERS and FITS, never that it runs
or how fast.  As in ``test_compile_fullsize_lamb.py``, the topology is
described inside a fixture: only one process at a time may load the TPU's
library.  (``test_compile_fullsize_lamb_pulled.py``, ``..._lamb_mixed.py``
and ``..._lamb.py`` describe the two passes over every key that one chip
made before, and fail at ``chips=1`` where they name ``lamb_apply``'s
results; their four-chip tests hold: PERF.md, section 7.)
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
VMEM = 128 * 2**20      # lamb_bytes.VMEM_BYTES: one v5e TensorCore's
TILE = 65536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # environment, not code
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compiled(topo, cell, op="push_pull_st"):
    """(compiled program, lowered text, total, padded, the engine's count
    of elements in one pass) of ``cell``'s bucket under the
    configuration's handle and dtypes on one described chip."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import (KEY_NO_ADAPT, KEY_NO_DECAY,
                                            CollectiveEngine, DenseBucket,
                                            _padded_len)

    with open(os.path.join(BENCH, "configs", f"{cell}.json")) as fh:
        config = json.load(fh)
    tensors = buckets.expand_tensors(config["tensors"])
    lens = np.array([n for _, n in tensors], dtype=np.int64)
    flags = np.array([
        (KEY_NO_DECAY | KEY_NO_ADAPT)
        if any(fnmatch.fnmatchcase(name, p)
               for p in config["no_decay_no_adapt"]) else 0
        for name, _ in tensors], dtype=np.int32)
    mesh = Mesh(np.array(topo.devices[:1]), ("kv",))
    handle = config["server_handle"]
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    total = int(lens.sum())
    padded = _padded_len(total, 1, True)
    job = jnp.dtype(config.get("job_dtype", "float32"))
    # The record alone: registering would allocate the store on a chip
    # that is described and not attached.
    bucket = DenseBucket(name="tree", keys=np.arange(398, dtype=np.uint64),
                         val_len=0, dtype=jnp.float32, total_len=total,
                         padded_len=padded, lens=lens, flags=flags,
                         **({} if job == jnp.float32 else {"job_dtype": job}))
    shard = NamedSharding(mesh, P("kv"))
    vec = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=shard)
    slot = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=shard)
    grads = jax.ShapeDtypeStruct(
        (1, total), job, sharding=NamedSharding(mesh, P("kv", None)))
    prog = eng._program(op, padded, jnp.float32, handle, bucket)
    lowered = prog.lower(vec, vec, vec, slot, grads)
    return (lowered.compile(), lowered.as_text(), total, padded,
            eng._lamb_plan(bucket).one_pass_len)


def _call(text, kernel):
    """(result shapes, scoped VMEM bytes) of the custom call
    ``%<kernel>.1`` of a compiled text."""
    (line,) = [l for l in text.splitlines()
               if l.lstrip().startswith(f"%{kernel}.1 = ")]
    results = re.findall(
        r"\w+\[[\d,]*\]", line.split(" = ", 1)[1].split(" custom-call(")[0])
    scoped = re.search(
        r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0",'
        r'"size":"(\d+)"\}\]', line)
    return results, int(scoped.group(1))


def _tree_sized(text, least):
    """(opcode, shape) of every operation of a compiled text, the kernels,
    parameters and what moves no value apart, whose first result holds at
    least ``least`` elements."""
    found = []
    for shape, dims, opcode in re.findall(
            r"^\s*(?:ROOT )?%[\w.\-]+ = \(?(\w+\[([\d,]*)\])\S* ([\w\-]+)\(",
            text, flags=re.M):
        n = int(np.prod([int(d) for d in dims.split(",") if d] or [1],
                        dtype=np.int64))
        if n >= least and opcode not in ("parameter", "get-tuple-element",
                                         "bitcast", "tuple", "custom-call"):
            found.append((opcode, shape))
    return found


@pytest.mark.parametrize("cell, short, itemsize", [
    ("bert-large-lamb", "f32", 4), ("bert-large-lamb-bf16", "bf16", 2)])
def test_on_one_chip_every_key_but_the_largest_takes_one_pass(
        topo, cell, short, itemsize):
    compiled, lowered, total, padded, one_pass = _compiled(topo, cell)
    assert (total, padded) == (336226108, 5131 * TILE)
    assert one_pass == total - 31254528 == 304971580
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    state = f"f32[{padded // 128},128]"
    # The first pass: emb.word's tiles (476.9 of them) and no other.
    results, scoped = _call(text, "lamb_moments")
    assert results == [state, state, "f32[796]"]
    assert "s32[477]" in text.split("%lamb_moments.1 = ")[1].split("\n")[0]
    assert scoped <= 16 * 2**20
    # The one pass: store, m, v in place, the pulled vector last, of the
    # job's dtype; the gradient its operand as the job handed it over.
    results, scoped = _call(text, "lamb_apply")
    assert results == [state] * 3 + [f"{short}[{total}]"]
    assert re.search(rf"= {short}\[1,{total}\]\S* parameter\(", text)
    # A ring of 65 tiles of p and of u, 32.5 MiB, and the default 16 MiB
    # for the eight streams' buffers: under half of the chip's VMEM.
    assert 2 * 65 * 4 * TILE <= scoped < VMEM // 2
    assert _tree_sized(text, total // 2) == []
    assert "all-gather" not in text and "convert" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * 4 * padded
    assert mem.temp_size_in_bytes < 10**7
    # Held as before: p, m, v, the gradient (a 2-byte row at the f32
    # row's bytes) and the pulled tree.
    args = 3 * 4 * padded + 4 * total + 4
    assert abs(mem.argument_size_in_bytes - args) < 10**4
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert args + itemsize * total <= held < args + itemsize * total + 10**7
    assert held < 0.5 * HBM


@pytest.mark.parametrize("cell", ["bert-large-lamb", "bert-large-lamb-bf16"])
def test_a_push_alone_takes_the_same_pass(topo, cell):
    compiled, lowered, total, padded, _ = _compiled(topo, cell, op="push_st")
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    results, _ = _call(text, "lamb_apply")
    assert results == [f"f32[{padded // 128},128]"] * 3
    assert _tree_sized(text, total // 2) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 10**7
