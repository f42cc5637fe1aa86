"""Compile-only: the one program ``moonlight-16b-muon.tree.4chip`` runs, at
full size, for a described ``v5e:2x2``: the bucket of 153 keys and
568,484,352 values of ``moonlight-16b-muon-4chip`` sharded on its keys'
borders over four owners, ``push_pull_st`` under the configuration's
``muon`` handle with four workers' rows ``f32[4, 568484352]``.

It asserts ONLY what every sound program of this deployment holds: store
and state are updated in place, what a chip holds while the program runs
stays under its memory, and each worker is handed one tree of
``total_len``.  It names no collective, no placing pass and no branch: a
program that exchanges otherwise (a reduce-scatter at half the bytes, the
exchange hidden behind the products) passes it unedited.  A compile that
passes says the program LOWERS and FITS, never that it runs or how fast.
The topology is described inside a fixture: only one process at a time may
load the TPU's library.
"""

import json
import os

import numpy as np
import pytest

import muon_flops
from conftest import BENCH

jax = pytest.importorskip("jax")

HBM = 16 * 10**9
CHIPS = 4


@pytest.fixture(scope="module")
def compiled():
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import (KEY_ELEMENTWISE,
                                            CollectiveEngine, DenseBucket)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # environment, not code
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with open(os.path.join(BENCH, "configs",
                           "moonlight-16b-muon-4chip.json")) as fh:
        config = json.load(fh)
    tensors = muon_flops.expand_shapes(config["tensors"])
    shapes = np.array([s for _, s in tensors], dtype=np.int64)
    lens = shapes[:, 0] * shapes[:, 1]
    flags = np.array([KEY_ELEMENTWISE
                      if muon_flops.is_adamw(n, config["adamw_keys"]) else 0
                      for n, _ in tensors], dtype=np.int32)
    total = int(lens.sum())
    assert (len(lens), total, config["chips"]) == (
        153, config["parameters"], CHIPS)
    mesh = Mesh(np.array(topo.devices[:CHIPS]), ("kv",))
    handle = config["server_handle"]
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    # The record alone, laid by its owners as the first push under ``muon``
    # would lay it: registering would allocate the store on chips that are
    # described and not attached.
    bucket = DenseBucket(name="tree", keys=np.arange(153, dtype=np.uint64),
                         val_len=0, dtype=jnp.float32, total_len=total,
                         padded_len=0, lens=lens, flags=flags, shapes=shapes)
    owners = bucket.owned = eng._owner_plan(bucket)
    bucket.padded_len = owners.padded_len
    shard = NamedSharding(mesh, P("kv"))
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=shard)
    state = [sds(s) for s in eng._muon_state_shapes(bucket)] + [
        sds((CHIPS,))]
    grads = jax.ShapeDtypeStruct(
        (CHIPS, total), jnp.float32,
        sharding=NamedSharding(mesh, P("kv", None)))
    prog = eng._program("push_pull_st", bucket.padded_len, jnp.float32,
                        handle, bucket)
    exe = prog.lower(sds((bucket.padded_len,)), *state, grads).compile()
    return exe, owners, state, total


def test_every_matrix_lies_whole_on_one_owner_and_the_owners_are_level(
        compiled):
    _, owners, _, total = compiled
    assert owners.matrices == 135 and owners.total_len == total
    assert owners.ns_flops == 20631616225280.0
    assert owners.padded_len <= 1.10 * total
    assert owners.flops.max() <= 1.2 * owners.flops.mean()
    assert (owners.where[owners.where[:, 0] >= 0, 0] < CHIPS).all()


def test_store_and_state_are_updated_in_place_and_the_step_fits(compiled):
    exe, owners, state, total = compiled
    mem = exe.memory_analysis()     # of one device
    held = 4 * owners.shard_len + sum(
        4 * int(np.prod(s.shape)) // CHIPS for s in state)
    # The store's shard and every array of the state, where they lie.
    assert mem.alias_size_in_bytes >= held
    # What a chip holds while the program runs: its shard of store and
    # state, its worker's row, the tree it is handed, the temporaries; and
    # what the worker still keeps, the step before's pulled tree.  (The
    # compiler's account, an upper bound: it reads 14.70 GB with XLA's
    # copies laying the tree, where the chip's own peak is 5 GB lower:
    # PERF.md section 4.)
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    print(f"argument {mem.argument_size_in_bytes:,} temp "
          f"{mem.temp_size_in_bytes:,} output {mem.output_size_in_bytes:,} "
          f"alias {mem.alias_size_in_bytes:,}: peak {peak:,} + the kept "
          f"tree {4 * total:,}")
    assert peak + 4 * total < HBM


def test_each_worker_is_handed_one_tree_of_total_len(compiled):
    exe, _, state, total = compiled
    outs = exe.output_shardings
    assert len(outs) == 1 + len(state) + 1
    shapes = [tuple(a.shape) for a in jax.tree_util.tree_leaves(
        exe.out_avals if hasattr(exe, "out_avals") else exe.out_info)]
    assert shapes[-1] == (total,)
    assert outs[-1].is_fully_replicated
