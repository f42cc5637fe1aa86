"""Compile-only: the program of ``bert-large-lamb-bf16.tree`` at full size,
for the v5e.  The job's gradient is the row ``bf16[1,336226108]`` that
``lamb_moments`` reads as it stands and the pulled tree the vector
``bf16[336226108]`` that ``lamb_apply`` leaves second: no operation outside
the two kernels has a result as large as the tree (no ``convert``, ``copy``,
``pad``, ``slice`` or ``reduce`` of 336 M values in either dtype), which is
the pass a mixed-precision deployment exists to save.  (The chip holds the
row in tiles of two rows, ``T(2,128)(2,1)``, at the f32 row's 1.345 GB; a
vector would lie packed: the last test.)  On four chips the gradient is
widened for the f32 sum and the pulled tree is the all-gather of the shards
rounded.  A compile that passes says a program LOWERS and FITS, never that
it runs or how fast.  As in ``test_compile_fullsize_lamb.py``, the topology
is described inside a fixture: only one process at a time may load the TPU's
library.
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
    under the configuration's handle and dtypes over ``chips`` described
    chips."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import (KEY_NO_ADAPT, KEY_NO_DECAY,
                                            CollectiveEngine, DenseBucket,
                                            _padded_len)

    with open(os.path.join(BENCH, "configs",
                           "bert-large-lamb-bf16.json")) as fh:
        config = json.load(fh)
    assert (config["dtype"], config["job_dtype"]) == ("float32", "bfloat16")
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
                         padded_len=padded, lens=lens, flags=flags,
                         job_dtype=jnp.bfloat16)
    shard = NamedSharding(mesh, P("kv"))
    vec = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=shard)
    slot = jax.ShapeDtypeStruct((chips,), jnp.float32, sharding=shard)
    grads = jax.ShapeDtypeStruct(
        (chips, total), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("kv", None)))
    prog = eng._program(op, padded, jnp.float32, handle, bucket)
    lowered = prog.lower(vec, vec, vec, slot, grads)
    return lowered.compile(), lowered.as_text(), total, padded


def _tree_sized(text, least):
    """(opcode, shape) of every operation of a compiled text, the two
    kernels, parameters and what moves no value apart, whose first result
    holds at least ``least`` elements."""
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


def test_on_one_chip_the_kernels_read_and_write_the_jobs_dtype(topo):
    compiled, lowered, total, padded = _compiled(topo, 1)
    assert (total, padded) == (336226108, 5131 * 65536)
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    rows = padded // 128
    # The gradient is the kernel's operand as the job handed it over, in
    # a 2-byte row's tiles; the pulled vector lies packed.
    assert f"bf16[1,{total}]{{1,0:T(2,128)(2,1)}}" in text
    assert f"bf16[{total}]{{0:T(1024)(128)(2,1)}}" in text
    moments = next(l for l in text.splitlines()
                   if l.lstrip().startswith("%lamb_moments.1 = "))
    assert f"(f32[{rows},128]" in moments
    operands = moments.split(" custom-call(")[1].split(")")[0]
    grad = re.search(r"%([\w.\-]+)$", operands.split(", ")[-1]).group(1)
    assert re.search(
        rf"%{re.escape(grad)} = bf16\[1,{total}\]\S* parameter\(", text), grad
    # The store first, in place, under the kernel's name; the pulled
    # vector second, in the job's dtype, and the program's result as it
    # stands.
    assert re.search(
        rf"%lamb_apply\.1 = \(f32\[{rows},128\]\S*, bf16\[{total}\]", text)
    assert _tree_sized(text, total) == []
    assert "convert" not in [op for op, _ in _tree_sized(text, total // 2)]
    assert "all-gather" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * 4 * padded
    assert mem.temp_size_in_bytes < 10**7
    # Held: p, m, v in f32 (4.03 GB), the bf16 gradient in its row's
    # tiles (1.34 GB) and the pulled tree in bf16, packed (0.67 GB).
    args = 3 * 4 * padded + 4 * total + 4
    assert abs(mem.argument_size_in_bytes - args) < 10**4
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert args + 2 * total <= held < args + 2 * total + 10**7
    assert 0.25 * HBM < held < 0.4 * HBM


def test_on_four_chips_the_sum_is_f32_and_the_rounded_shards_are_gathered(
        topo):
    compiled, lowered, total, padded = _compiled(topo, 4)
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    rows = padded // 4 // 128
    assert f"%lamb_apply.1 = f32[{rows},128]" in text
    assert f"%lamb_moments.1 = (f32[{rows},128]" in text
    # Widened before the sum over W: what crosses the chips for the
    # reduction is f32, what the all-gather carries is the job's dtype.
    assert re.search(r"= f32\[\d+(,\d+)?\]\S* (all-reduce|reduce-scatter)\(",
                     text)
    assert re.search(rf"= bf16\[{padded}\]\S* all-gather\(", text)
    assert "ps.push.widen" in text and "ps.pull.narrow" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 3 * 4 * padded // 4


def test_a_push_alone_has_the_one_result_kernel(topo):
    compiled, lowered, total, padded = _compiled(topo, 1, op="push_st")
    text = compiled.as_text()
    assert f"%lamb_apply.1 = f32[{padded // 128},128]" in text
    assert _tree_sized(text, total) == []


def test_a_bf16_row_takes_the_f32_rows_bytes(topo):
    """What the engine's ``[W, total]`` contract costs a 2-byte job on the
    chip: XLA lays ``bf16[1, n]`` in tiles of two rows, half of each
    padding, where a vector lies packed (PERF.md, PR 41, Open questions)."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    n = 336226108
    one = SingleDeviceSharding(topo.devices[0])
    sizes = {}
    for name, shape in (("row", (1, n)), ("vector", (n,))):
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
        compiled = jax.jit(lambda a: a + a).lower(x).compile()
        sizes[name] = compiled.memory_analysis().argument_size_in_bytes
        if name == "row":
            assert "T(2,128)(2,1)" in compiled.as_text()
    assert abs(sizes["vector"] - 2 * n) < 10**5
    assert abs(sizes["row"] - 4 * n) < 10**5
