"""The engine's Pallas kernels must pass REAL-TPU Mosaic lowering, not
just the CPU interpreter — and must be IN the program lowered for a TPU
mesh even though this process's default backend is the CPU.

``jax.experimental.topologies`` provides compile-only AOT device sets
for named TPU topologies; lowering + compiling the engine's programs
against one runs the same Mosaic pipeline a real v5e-8 slice would, with
no chips.  That a program lowers says nothing about whether it runs:
``chip_smoke.py`` on the chip does.  Skips (not fails) when the topology
client is unavailable (no libtpu / no compile service) —
tools/aot_ring_compile.py is the full sweep whose committed report is
docs/AOT_RING.json.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


@pytest.fixture(scope="module")
def v5e8_mesh():
    import subprocess
    import sys

    from jax.sharding import Mesh

    # get_topology_desc initializes the TPU PJRT plugin, and libtpu's
    # init can block for MINUTES inside a GIL-holding C call (e.g. 30
    # retries per GCP instance-metadata variable when the metadata
    # service answers 403) — neither a thread deadline nor pytest can
    # preempt it, and it eats the whole tier-1 wall budget before the
    # except-and-skip below ever fires.  Probe in a child process with
    # a hard deadline first: only when the child proves the plugin
    # answers promptly do we pay the in-process init.
    probe = (
        "from jax.experimental import topologies\n"
        "topologies.get_topology_desc("
        "platform='tpu', topology_name='v5e:2x4')\n"
        "print('TOPO_OK')\n"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", probe],
            capture_output=True, text=True, timeout=60.0,
        )
    except subprocess.TimeoutExpired:
        pytest.skip("TPU AOT topology probe exceeded 60 s "
                    "(TPU plugin init wedged)")
    if "TOPO_OK" not in out.stdout:
        tail = (out.stderr.strip() or out.stdout.strip())[-300:]
        pytest.skip(f"TPU AOT topology unavailable: {tail!r}")

    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x4"
        )
    except Exception as exc:  # noqa: BLE001 - environment, not code
        pytest.skip(f"TPU AOT topology unavailable: {exc!r}")
    return Mesh(np.array(topo.devices).reshape(8), ("kv",))


def test_ring_kernel_compiles_for_real_v5e(v5e8_mesh):
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import CollectiveEngine

    eng = CollectiveEngine(mesh=v5e8_mesh, impl="pallas")
    assert eng._effective_impl(jnp.float32, "sum") == "pallas"
    padded = 8 * 65536
    prog = eng._ring_program(padded, jnp.float32, "_default")
    store = jax.ShapeDtypeStruct(
        (padded,), jnp.float32, sharding=NamedSharding(v5e8_mesh, P("kv"))
    )
    # FLAT grads: the 1-D ring program's parameter form (a (1, padded)
    # per-device block would sublane-pad 2-byte dtypes to 2x the bytes
    # — engine._prep_grads_ring).
    grads = jax.ShapeDtypeStruct(
        (8 * padded,), jnp.float32,
        sharding=NamedSharding(v5e8_mesh, P("kv")),
    )
    lowered = prog.lower(store, grads)
    # The kernel must actually be in the program (Mosaic custom call),
    # not silently replaced by an XLA fallback.
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()  # full Mosaic + XLA pipeline
    assert compiled.as_text()


def _ring_args(mesh, padded):
    from jax.sharding import NamedSharding, PartitionSpec as P

    import jax.numpy as jnp

    sharding = NamedSharding(mesh, P("kv"))
    return (
        jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=sharding),
        jax.ShapeDtypeStruct((8 * padded,), jnp.float32, sharding=sharding),
    )


def test_ring_states_its_vmem_need_at_a_real_width(v5e8_mesh):
    """A 32 MiB bucket keeps 24 MiB resident per device — beyond Mosaic's
    default 16 MiB scoped limit, inside the kernel's budget: it compiles
    because the kernel asks for what it needs."""
    import jax.numpy as jnp

    from pslite_tpu.parallel.engine import CollectiveEngine

    eng = CollectiveEngine(mesh=v5e8_mesh, impl="pallas")
    padded = 8 << 20
    lowered = eng._ring_program(padded, jnp.float32, "_default").lower(
        *_ring_args(v5e8_mesh, padded))
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


def test_ring_refuses_a_chunk_beyond_its_vmem_budget(v5e8_mesh):
    import jax.numpy as jnp

    from pslite_tpu.ops.ring_collective import VMEM_BUDGET_BYTES
    from pslite_tpu.parallel.engine import CollectiveEngine

    eng = CollectiveEngine(mesh=v5e8_mesh, impl="pallas")
    padded = 8 * (VMEM_BUDGET_BYTES // 4 // 4)  # 6 x chunk bytes > budget
    with pytest.raises(ValueError, match=f"budget is {VMEM_BUDGET_BYTES}"):
        eng._ring_program(padded, jnp.float32, "_default").lower(
            *_ring_args(v5e8_mesh, padded))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "handle", ["sgd_momentum:0.01,0.9", "adam:1e-3", "adagrad:0.01"])
def test_fused_handle_is_a_mosaic_kernel_in_push_pull_st(
        v5e8_mesh, handle, dtype):
    """Lowered for a TPU mesh from this CPU-default process, the fused
    optimizer is a Mosaic kernel — never the interpreter — and compiles
    for v5e in bf16 too (arithmetic in f32, one rounding on the store)."""
    import os
    import sys

    import jax.numpy as jnp

    from pslite_tpu.parallel.engine import CollectiveEngine

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from aot_ring_compile import _compile_stateful

    assert jax.devices()[0].platform == "cpu"
    eng = CollectiveEngine(mesh=v5e8_mesh)
    assert not eng._interpret
    padded = 8 * 100_000  # not tile-aligned per shard
    row = _compile_stateful(eng, v5e8_mesh, handle, padded,
                            jnp.dtype(dtype))
    assert row["mosaic_custom_call"]
