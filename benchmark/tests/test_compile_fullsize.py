"""Compile-only: every program the cells run, at full size, for the v5e,
with what it would hold on a device.  The TPU's compiler is installed in
the sandbox and compiles for a chip that is described and not attached; a
compile that passes says a program LOWERS and FITS, never that it runs or
how fast.  All in this one file, topology described inside a fixture: only
one process at a time may load the TPU's library.
"""

import json
import os

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


@pytest.fixture(scope="module")
def meshes(topo):
    from jax.sharding import Mesh

    return {1: Mesh(np.array(topo.devices[:1]), ("kv",)),
            4: Mesh(np.array(topo.devices), ("kv",))}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


def _bucket_lengths(config):
    with open(os.path.join(BENCH, "traffic", "device-buckets.json")) as fh:
        limit = json.load(fh)["bucket_elements"]
    sizes = [n for _, n in buckets.expand_tensors(config["tensors"])]
    return sorted(set(buckets.make_buckets(sizes, limit)))


@pytest.mark.parametrize("name", ["bert-large-adam", "gpt2-large-adam"])
def test_dense_programs_compile_with_the_fused_kernel(name, meshes):
    """``push_pull_st`` under the configuration's Adam handle, for every
    distinct bucket length of the cell, on the cell's number of chips."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import CollectiveEngine

    config = _config(name)
    mesh = meshes[config["chips"]]
    W = config["chips"]
    eng = CollectiveEngine(mesh=mesh, server_handle=config["server_handle"])
    lengths = _bucket_lengths(config)
    assert len(lengths) == 7
    shard = NamedSharding(mesh, P("kv"))
    for n in lengths:
        padded = -(-n // W) * W
        vec = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=shard)
        slot = jax.ShapeDtypeStruct((W,), jnp.float32, sharding=shard)
        grads = jax.ShapeDtypeStruct(
            (W, padded), jnp.float32,
            sharding=NamedSharding(mesh, P("kv", None)))
        prog = eng._program("push_pull_st", padded, jnp.float32,
                            config["server_handle"])
        lowered = prog.lower(vec, vec, vec, slot, grads)
        assert "tpu_custom_call" in lowered.as_text(), n
        compiled = lowered.compile()
        if W > 1:
            # Seen here (PR 23): the v5e compiler lowers the engine's
            # psum_scatter to an all-reduce and a slice, and for small
            # buckets the all_gather to an all-reduce too.  Any of them
            # crosses the chips.
            text = compiled.as_text()
            assert any(c in text for c in ("all-reduce", "reduce-scatter",
                                           "all-gather")), n
        mem = compiled.memory_analysis()
        # p, m, v are donated: the program updates them in place.
        assert mem.alias_size_in_bytes >= 3 * 4 * padded // W


def test_sparse_bodies_compile_in_place_over_the_full_table(meshes):
    """The push body aliases its 10.24 GB store and holds no second table;
    the pull's output is one batch of rows."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel import sparse

    config = _config("dlrm-criteo-emb")
    with open(os.path.join(BENCH, "traffic", "zipf-rows.json")) as fh:
        lookups = json.load(fh)["lookups_per_worker"]
    mesh = meshes[1]
    rows, dim = config["rows"], config["dim"]
    table_bytes = rows * dim * 4
    assert table_bytes == 10_240_000_000
    rows2 = NamedSharding(mesh, P("kv", None))
    store = jax.ShapeDtypeStruct((rows, dim), jnp.float32, sharding=rows2)
    idx = jax.ShapeDtypeStruct((1, lookups), jnp.int32, sharding=rows2)
    grads = jax.ShapeDtypeStruct(
        (1, lookups, dim), jnp.float32,
        sharding=NamedSharding(mesh, P("kv", None, None)))

    push = jax.jit(jax.shard_map(
        lambda st, ix, g: sparse._scatter_rows("kv", 1, rows, 1, dim, st,
                                               ix, g),
        mesh=mesh,
        in_specs=(P("kv", None), P("kv", None), P("kv", None, None)),
        out_specs=P("kv", None), check_vma=False), donate_argnums=(0,))
    mem = push.lower(store, idx, grads).compile().memory_analysis()
    assert mem.alias_size_in_bytes == table_bytes
    assert mem.temp_size_in_bytes < 10**9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < HBM

    pull = jax.jit(jax.shard_map(
        lambda st, ix: sparse._pull_rows("kv", 1, st, ix, pack=1, dim=dim),
        mesh=mesh, in_specs=(P("kv", None), P("kv", None)),
        out_specs=P("kv", None), check_vma=False))
    mem = pull.lower(store, idx).compile().memory_analysis()
    assert mem.output_size_in_bytes == lookups * dim * 4
    assert mem.temp_size_in_bytes < 10**9
