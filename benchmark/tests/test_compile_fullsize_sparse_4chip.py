"""Compile-only, beside ``test_compile_fullsize_packed.py``: the two programs
of the cell ``dlrm-criteo-emb.zipf.4chip`` at full size for a described
``v5e:2x2``, 80,000,000 rows of 128 f32 lanes sharded by ``row % 4`` over
four chips, 131,072 lookups a worker: the ``sum`` push
(``parallel/sparse.py`` ``_scatter_rows``) and the pull (``_pull_rows``).

It asserts ONLY what every sound program of this deployment holds: the push
writes its 10.24 GB shard in place, a step's programs fit a chip beside the
shard, and the pull hands each worker one batch.  It does NOT name the
collectives the exchange is made of, their shapes, or how many slots a shard
sorts: a program that routes by owner must pass it unedited.  A compile that
passes says the programs LOWER and FIT, never that they run or how fast.  The
topology is described inside a fixture: only one process at a time may load
the TPU's library.
"""

import json
import os

import numpy as np
import pytest

from conftest import BENCH

jax = pytest.importorskip("jax")

HBM = 16 * 10**9
CHIPS = 4


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    from jax.sharding import Mesh

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # environment, not code
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices[:CHIPS]), ("kv",))


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def sizes():
    config = _json("configs", "dlrm-criteo-emb-4chip.json")
    lookups = _json("traffic", "zipf-rows.json")["lookups_per_worker"]
    rows, dim = config["rows"], config["dim"]
    assert (rows, dim, lookups, config["chips"]) == (
        80_000_000, 128, 131_072, CHIPS)
    assert config["server_handle"] == "sum" and config["reduced"] == ["rows"]
    return rows, dim, lookups


def _shapes(mesh, rows, dim, lookups):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    return (sds((rows, dim), jnp.float32, P("kv", None)),
            sds((CHIPS, lookups), jnp.int32, P("kv", None)),
            sds((CHIPS, lookups, dim), jnp.float32, P("kv", None, None)))


def test_push_writes_its_shard_in_place_and_fits_a_chip(mesh, sizes):
    from jax.sharding import PartitionSpec as P

    from pslite_tpu.parallel import sparse

    rows, dim, lookups = sizes
    rps = rows // CHIPS
    shard_bytes = rps * dim * 4
    assert shard_bytes == 10_240_000_000
    store, idx, grads = _shapes(mesh, rows, dim, lookups)

    def body(st, ix, g):
        new = sparse._scatter_rows("kv", CHIPS, rps, 1, dim, st, ix, g)
        return new, new[:1, :1]                # the engine's own outputs

    push = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("kv", None), P("kv", None), P("kv", None, None)),
        out_specs=(P("kv", None), P("kv", None)), check_vma=False),
        donate_argnums=(0,))
    mem = push.lower(store, idx, grads).compile().memory_analysis()
    # Per device: the shard is aliased (no second table), and the program
    # with the shard, its inputs and whatever it keeps meanwhile fits.
    assert mem.alias_size_in_bytes == shard_bytes
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < HBM, peak


def test_pull_hands_each_worker_one_batch_and_fits_a_chip(mesh, sizes):
    from jax.sharding import PartitionSpec as P

    from pslite_tpu.parallel import sparse

    rows, dim, lookups = sizes
    store, idx, _ = _shapes(mesh, rows, dim, lookups)
    pull = jax.jit(jax.shard_map(
        lambda st, ix: sparse._pull_rows("kv", CHIPS, st, ix),
        mesh=mesh, in_specs=(P("kv", None), P("kv", None)),
        out_specs=P("kv", None), check_vma=False))
    mem = pull.lower(store, idx).compile().memory_analysis()
    assert mem.alias_size_in_bytes == 0
    assert mem.output_size_in_bytes == lookups * dim * 4 == 67_108_864
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM
