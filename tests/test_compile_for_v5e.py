"""The engines' Pallas kernels must pass REAL-TPU Mosaic lowering, not
just the CPU interpreter — and must be IN the program lowered for a TPU
mesh even though this process's default backend is the CPU.

``jax.experimental.topologies`` provides compile-only AOT device sets
for named TPU topologies; lowering + compiling the engines' programs
against one runs the same Mosaic pipeline a real v5e-8 slice would, with
no chips: the fused optimizer handles, the sparse kernels and pushes,
LAMB, mixed precision and Muon at full size.  That a program lowers says
nothing about whether it runs: ``chip_smoke.py`` on the chip does.  Skips
(not fails) when the topology client is unavailable (no libtpu / no
compile service).
"""

import os

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


def _compile_stateful(eng, mesh, handle: str, padded: int, dtype) -> bool:
    """Lower + compile the engine's ``push_pull_st`` program (XLA
    reduce-scatter, fused Pallas optimizer pass, XLA all-gather); whether
    a Mosaic kernel is in what was lowered."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = eng.num_shards
    vec = jax.ShapeDtypeStruct((padded,), dtype,
                               sharding=NamedSharding(mesh, P(eng.axis)))
    n_state, _ = eng._stateful_handle(handle)
    state = [vec] * min(n_state, 2)
    if n_state == 3:  # adam's per-shard step counter
        state.append(jax.ShapeDtypeStruct(
            (n,), jnp.float32, sharding=NamedSharding(mesh, P(eng.axis))))
    grads = jax.ShapeDtypeStruct(
        (n, padded), dtype,
        sharding=NamedSharding(mesh, P(eng.axis, None)))
    lowered = eng._program("push_pull_st", padded, dtype, handle).lower(
        vec, *state, grads)
    lowered.compile()
    return "tpu_custom_call" in lowered.as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "handle", ["sgd_momentum:0.01,0.9", "adam:1e-3", "adagrad:0.01"])
def test_fused_handle_is_a_mosaic_kernel_in_push_pull_st(
        v5e8_mesh, handle, dtype):
    """Lowered for a TPU mesh from this CPU-default process, the fused
    optimizer is a Mosaic kernel — never the interpreter — and compiles
    for v5e in bf16 too (arithmetic in f32, one rounding on the store)."""
    import jax.numpy as jnp

    from pslite_tpu.parallel.engine import CollectiveEngine

    assert jax.devices()[0].platform == "cpu"
    eng = CollectiveEngine(mesh=v5e8_mesh)
    assert not eng._interpret
    padded = 8 * 100_000  # not tile-aligned per shard
    assert _compile_stateful(eng, v5e8_mesh, handle, padded,
                             jnp.dtype(dtype))


# -- the sparse table's write by distinct row (ops/row_add.py) ------------------


@pytest.fixture(scope="module")
def v5e_chip(v5e8_mesh):
    from jax.sharding import Mesh

    return Mesh(np.array([v5e8_mesh.devices.flat[0]]), ("kv",))


def _segment_sum_is_the_kernel(text, m, scope):
    """The compiled push sums its segments with ``ops/segment_sum.py``: one
    Mosaic call named ``%segment_sum`` whose result is the batch's
    ``f32[m,128]``, under ``scope``, and no scatter of that shape is left
    (XLA's scatter-add into the workspace was one)."""
    sums = [l for l in text.splitlines()
            if l.replace("ROOT ", "").lstrip().startswith("%segment_sum")]
    assert len(sums) == 1, sums
    assert f"= f32[{m},128]" in sums[0] and "tpu_custom_call" in sums[0]
    assert scope in sums[0] and "segment_sum/pallas_call" in sums[0]
    assert not [l for l in text.splitlines()
                if " scatter(" in l and f"= f32[{m},128]" in l]


def _acc_update_is_the_kernel(text, rows, m):
    """The compiled push updates the accumulator with ``ops/acc_update.py``:
    one Mosaic call named ``%acc_update`` under ``ps.update`` whose first
    result is the accumulator as 128-lane rows, a bitcast of the donated
    ``f32[rows]`` either way, so no operation but parameter and bitcast has
    the accumulator for its result: no scatter, no copy; and no gather
    leaves the batch's ``f32[m]``."""
    lines = [l.replace("ROOT ", "").strip() for l in text.splitlines()]
    calls = [l for l in lines if l.startswith("%acc_update")]
    assert len(calls) == 1, calls
    assert f"(f32[{rows // 128},128]" in calls[0]
    assert "tpu_custom_call" in calls[0] and "ps.update" in calls[0]
    assert "acc_update/pallas_call" in calls[0]
    whole = [l for l in lines if f"= f32[{rows}]" in l
             or f"= f32[{rows // 128},128]" in l]
    assert whole and all(
        " parameter(" in l or " bitcast(" in l or " get-tuple-element(" in l
        for l in whole), whole
    assert not [l for l in lines if f"= f32[{m}]" in l and "/gather" in l]
    assert not [l for l in lines if " scatter(" in l and f"f32[{rows}]" in l]


@pytest.mark.parametrize("m", [12, 1500, 4096, 131_072])
def test_row_add_compiles_for_v5e_in_place(v5e_chip, m):
    """The kernel lowers through Mosaic at a real table size, for a batch
    smaller than a block of row ids, one that is no whole number of
    blocks, ``chip_smoke.py``'s and the cell's, and its one result is the
    donated table itself: nothing of the table's size is allocated or
    copied beside it."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.ops.row_add import row_add

    rows, width = 20_000_000, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(v5e_chip, P()))

    compiled = jax.jit(
        lambda s, r, d, n: row_add(s, r, d, n, interpret=False),
        donate_argnums=(0,),
    ).lower(sds((rows, width), jnp.float32), sds((m,), jnp.int32),
            sds((m, width), jnp.float32), sds((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == rows * width * 4
    assert mem.temp_size_in_bytes < 4 << 20      # a padded batch, at most
    whole = [l for l in compiled.as_text().splitlines()
             if f"= f32[{rows},{width}]" in l and " parameter(" not in l]
    assert len(whole) == 1 and "tpu_custom_call" in whole[0], whole
    assert " %row_add" in whole[0]


@pytest.mark.parametrize("m", [12, 1500, 53_248, 131_072])
def test_segment_sum_compiles_for_v5e(v5e_chip, m):
    """``ops/segment_sum.py`` lowers through Mosaic for a batch below one
    block of slots, one that is no whole number of blocks (padded, and cut
    back), and the two cells' own, whose one result of the batch's size is
    the kernel's: no copy of it beside it."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.ops.segment_sum import _BLOCK, segment_sum

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(v5e_chip, P()))

    compiled = jax.jit(
        lambda seg, sg: segment_sum(seg, sg, interpret=False),
    ).lower(sds((m,), jnp.int32), sds((m, 128), jnp.float32)).compile()
    text = compiled.as_text()
    padded = -(-m // _BLOCK) * _BLOCK
    _segment_sum_is_the_kernel(text, padded, "segment_sum")
    if padded == m:
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
        assert not [l for l in text.splitlines() if " copy(" in l
                    and f"= f32[{m},128]" in l]


@pytest.mark.parametrize("rows, m", [
    (20_000_000, 12), (20_000_000, 1500), (20_000_000, 16_384),
    (20_000_000, 131_072),
    # Accumulators kept in whole 128s for tables of 3 to 108, 20,265 and
    # 590,152 rows (``dlrm-dcnv2-multihot``): one row of 128, under a tile's
    # eight sublanes; 159 rows; 4,611 rows, 18 tiles and a ragged nineteenth.
    (128, 4_096), (20_352, 24_576), (590_208, 40_960)])
def test_acc_update_compiles_for_v5e_in_place(v5e_chip, rows, m):
    """``ops/acc_update.py`` lowers through Mosaic over the cell's
    accumulator (156,250 rows of 128: a ragged last tile, and no multiple
    of the 1,024 a 1-D array is tiled by) for a batch below one chunk of
    ids, one that is no whole number of chunks, ``chip_smoke.py``'s on four
    chips and the cell's, and over small accumulators kept in whole 128s;
    the accumulator is donated and updated in place, seen as 128-lane rows
    through bitcasts, with nothing of its size allocated or copied beside
    it."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.ops.acc_update import acc_update


    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(v5e_chip, P()))

    compiled = jax.jit(
        lambda a, r, g, n: acc_update(a, r, g, n, interpret=False),
        donate_argnums=(0,),
    ).lower(sds((rows,), jnp.float32), sds((m,), jnp.int32),
            sds((m,), jnp.float32), sds((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert rows * 4 <= mem.alias_size_in_bytes < rows * 4 + (1 << 16)
    assert mem.temp_size_in_bytes < 1 << 20
    text = compiled.as_text()
    calls = [l for l in text.splitlines()
             if l.replace("ROOT ", "").lstrip().startswith("%acc_update")]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0], calls
    assert f"(f32[{rows // 128},128]" in calls[0]
    whole = [l for l in text.splitlines()
             if f"= f32[{rows}]" in l or f"= f32[{rows // 128},128]" in l]
    # (A small accumulator the compiler moves through its alternate memory,
    # ``S(1)``, around the kernel: a ``copy-start`` / ``copy-done`` pair
    # each way, of 512 B to 80 KB here; nothing of 80 MB fits there.)
    moved = (" copy-start(", " copy-done(") if rows < 1 << 20 else ()
    assert whole and all(
        " parameter(" in l or " bitcast(" in l or " get-tuple-element(" in l
        or any(op in l for op in moved)
        for l in whole), whole


@pytest.mark.parametrize("kept", [False, True])
def test_row_adagrad_push_at_full_size_writes_the_table_with_row_add(
        v5e_chip, kept, tmp_path, monkeypatch):
    """The program ``benchmark/tests/test_compile_fullsize_handle.py``
    compiles (the cell ``dlrm-criteo-rowadagrad.zipf``'s push, called as
    that test calls it, from this CPU-default process): lowered for the
    v5e its table write is the ``row_add`` kernel under the scope
    ``ps.sparse.push.scatter_add``, no scatter has the table for its
    result, the segments are summed by the ``segment_sum`` kernel under
    ``ps.sparse.combine``, the accumulator is updated by the ``acc_update``
    kernel under ``ps.update`` (no gather of the batch's ``f32[131072]``,
    no scatter into ``f32[20000000]``, no copy of the accumulator: its view
    as 128-lane rows is a bitcast), and both donations still hold.  That is
    compiled once, for ``kept``: the program a later process builds from
    the three kernels' traces as the compile cache's directory keeps them,
    without tracing any (the same module, so the same compiled program, as
    the one traced in place, of which only the lowering is looked at)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.ops import acc_update as acc_update_module
    from pslite_tpu.ops import row_add as row_add_module
    from pslite_tpu.ops import segment_sum as segment_sum_module
    from pslite_tpu.parallel import sparse
    from pslite_tpu.utils import compile_cache

    assert jax.devices()[0].platform == "cpu"
    traced, summed, stepped = [], [], []
    real_acc = acc_update_module._acc_update
    monkeypatch.setattr(
        acc_update_module, "_acc_update",
        lambda acc, rows_, *rest: stepped.append(
            (acc.shape, rows_.shape)) or real_acc(acc, rows_, *rest))
    real = row_add_module._row_add
    monkeypatch.setattr(
        row_add_module, "_row_add",
        lambda store, *rest: traced.append(store.shape) or real(store, *rest))
    real_sum = segment_sum_module._segment_sum
    monkeypatch.setattr(
        segment_sum_module, "_segment_sum",
        lambda seg, sg, *rest: summed.append(sg.shape) or real_sum(
            seg, sg, *rest))
    if kept:
        monkeypatch.setattr(compile_cache, "_trace_dir",
                            lambda: str(tmp_path))
    rows, dim, lookups = 20_000_000, 128, 131_072

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(v5e_chip, spec))

    def body(st, ac, ix, g, lr, eps):
        new, acc_new = sparse._adagrad_sparse("kv", 1, rows, 1, dim, st, ac,
                                              ix, g, lr, eps)
        return new, acc_new, new[:1, :1]

    def push():
        return jax.jit(jax.shard_map(
            body, mesh=v5e_chip,
            in_specs=(P("kv", None), P("kv"), P("kv", None),
                      P("kv", None, None), P(), P()),
            out_specs=(P("kv", None), P("kv"), P("kv", None)),
            check_vma=False), donate_argnums=(0, 1))

    scalar = sds((), jnp.float32, P())
    args = (sds((rows, dim), jnp.float32, P("kv", None)),
            sds((rows,), jnp.float32, P("kv")),
            sds((1, lookups), jnp.int32, P("kv", None)),
            sds((1, lookups, dim), jnp.float32, P("kv", None, None)),
            scalar, scalar)
    lowered = push().lower(*args)
    assert traced == [(rows, dim)] and summed == [(lookups, dim)]
    assert stepped == [((rows,), (lookups,))]
    if not kept:
        text = lowered.as_text(debug_info=True)
        assert "tpu_custom_call" in text and "row_add" in text
        assert "segment_sum" in text and "acc_update" in text
        assert "ps.sparse.push.scatter_add" in text
        return
    compile_cache._traced.clear()               # a new process
    lowered = push().lower(*args)
    assert traced == [(rows, dim)]              # none is traced again
    assert summed == [(lookups, dim)]
    assert stepped == [((rows,), (lookups,))]
    assert len(os.listdir(tmp_path)) == 3       # a file a kernel
    compiled = lowered.compile()
    _acc_update_is_the_kernel(compiled.as_text(), rows, lookups)
    table = [l for l in compiled.as_text().splitlines()
             if f"= f32[{rows},{dim}]" in l and " parameter(" not in l]
    assert len(table) == 1, table
    assert " %row_add" in table[0] and "tpu_custom_call" in table[0]
    assert "ps.sparse.push.scatter_add" in table[0]
    assert " scatter(" not in table[0] and " copy(" not in table[0]
    _segment_sum_is_the_kernel(compiled.as_text(), lookups,
                               "ps.sparse.combine")
    mem = compiled.memory_analysis()
    state = rows * dim * 4 + rows * 4
    assert state <= mem.alias_size_in_bytes < state + (1 << 20)
    # Two workspaces of the batch's size in HBM (64 MiB each: the gathered
    # gradients in sorted order, and their sums, which XLA moves off the
    # chip's own memory before the accumulator's kernel: it keeps nothing
    # there across a custom call) and the ids.
    batch = lookups * dim * 4
    assert 2 * batch <= mem.temp_size_in_bytes < 2 * batch + (4 << 20)


def test_sum_push_at_full_size_combines_and_writes_the_table_with_row_add(
        v5e_chip):
    """The cell ``dlrm-criteo-emb.zipf``'s push (``_scatter_rows``, called
    as ``benchmark/tests/test_compile_fullsize.py`` calls it) lowered for
    the v5e: the table's one result is the ``row_add`` kernel's, in place
    over the donated table, no scatter has the table for its result, the
    combine's sorts stand before it, and the workspaces are of the batch's
    size."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel import sparse

    rows, dim, lookups = 20_000_000, 128, 131_072

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(v5e_chip, spec))

    compiled = jax.jit(jax.shard_map(
        lambda st, ix, g: sparse._scatter_rows("kv", 1, rows, 1, dim, st,
                                               ix, g),
        mesh=v5e_chip,
        in_specs=(P("kv", None), P("kv", None), P("kv", None, None)),
        out_specs=P("kv", None), check_vma=False), donate_argnums=(0,),
    ).lower(sds((rows, dim), jnp.float32, P("kv", None)),
            sds((1, lookups), jnp.int32, P("kv", None)),
            sds((1, lookups, dim), jnp.float32, P("kv", None, None))
            ).compile()
    text = compiled.as_text()
    table = [l for l in text.splitlines()
             if f"= f32[{rows},{dim}]" in l and " parameter(" not in l]
    assert len(table) == 1, table
    assert " %row_add" in table[0] and "tpu_custom_call" in table[0]
    assert "ps.sparse.push.scatter_add" in table[0]
    assert " scatter(" not in table[0] and " copy(" not in table[0]
    assert "ps.sparse.combine/sort" in text
    _segment_sum_is_the_kernel(text, lookups, "ps.sparse.combine")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == rows * dim * 4
    # The gathered gradients in sorted order and their segment sums (64 MiB
    # each at this batch: the kernel's result is the workspace the scatter
    # had), and the ids.
    batch = lookups * dim * 4
    assert 2 * batch <= mem.temp_size_in_bytes < 2 * batch + (1 << 20)


@pytest.mark.parametrize("op", ["push", "push_row_adagrad", "pull"])
def test_the_exchange_routed_by_owner_over_four_chips(v5e8_mesh, op):
    """The three sparse programs over four chips of the topology (4,096
    lookups a worker, 2^20 rows of 128 f32 lanes a shard: small, so
    that tier-1 can afford it; ``benchmark/tests/
    test_compile_fullsize_sparse_4chip.py`` compiles the cell's size).  The
    routed body and the gathered one are the branches of ONE conditional,
    through which the donated table, and under the handle the accumulator,
    pass in place; the routed branch's exchanges are two ``all-to-all``
    instructions named as such, ids and rows, and SYNCHRONOUS ones: the benchmark's
    ``sparse_route_ms`` counts an asynchronous collective's two ends and not
    the transfer between them (``PERF.md`` section 7), so a compiler that
    split them would be read too low.  The gathered branch keeps its
    all-gathers, and the pull's reduction of rows is in that branch alone."""
    import re

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel import sparse

    S, rps, dim, n = 4, 1 << 20, 128, 4096
    mesh = Mesh(np.array(list(v5e8_mesh.devices.flat[:S])), ("kv",))
    assert sparse._routes(S, n) and sparse._slots(S, n) == 6 * n // 4

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    store = sds((S * rps, dim), jnp.float32, "kv", None)
    acc = sds((S * rps,), jnp.float32, "kv")
    idx = sds((S, n), jnp.int32, "kv", None)
    g = sds((S, n, dim), jnp.float32, "kv", None, None)
    count = sds((S,), jnp.int32, "kv")
    scalar = sds((), jnp.float32)
    rows_spec, vec_spec = P("kv", None), P("kv")

    def push(st, ix, gr, c):
        over = []
        new = sparse._scatter_rows("kv", S, rps, 1, dim, st, ix, gr, over)
        return (new, new[:1, :1], *sparse._counted((c,), over))

    def push_row_adagrad(st, ac, ix, gr, lr, eps, c):
        over = []
        new, ac2 = sparse._adagrad_sparse("kv", S, rps, 1, dim, st, ac, ix,
                                          gr, lr, eps, over)
        return (new, ac2, new[:1, :1], *sparse._counted((c,), over))

    def pull(st, ix, c):
        over = []
        rows = sparse._pull_rows("kv", S, st, ix, over=over)
        return (rows, *sparse._counted((c,), over))

    body, in_specs, out_specs, args, donate = {
        "push": (push, (rows_spec, rows_spec, P("kv", None, None), vec_spec),
                 (rows_spec, rows_spec, vec_spec), (store, idx, g, count),
                 (0, 3)),
        "push_row_adagrad": (
            push_row_adagrad,
            (rows_spec, vec_spec, rows_spec, P("kv", None, None), P(), P(),
             vec_spec),
            (rows_spec, vec_spec, rows_spec, vec_spec),
            (store, acc, idx, g, scalar, scalar, count), (0, 1, 6)),
        "pull": (pull, (rows_spec, rows_spec, vec_spec),
                 (rows_spec, vec_spec), (store, idx, count), (2,)),
    }[op]
    compiled = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False), donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    lines = [l.replace("ROOT ", "").strip() for l in text.splitlines()]
    assert len([l for l in lines if " conditional(" in l]) == 1
    kinds = re.findall(r" (all-to-all|all-gather|all-reduce|reduce-scatter"
                       r"|collective-permute)(-start|-done)?\(", text)
    assert kinds.count(("all-to-all", "")) == 2
    assert not [k for k in kinds if k[0] == "all-to-all" and k[1]]
    a2a = [l for l in lines if " all-to-all(" in l]
    # A device trace lists an operation by its instruction's name, and the
    # benchmark's readers tell a collective by it: the routed exchange's
    # are named after their opcodes (``sparse._by_opcode``), not after
    # JAX's primitives (``%all_to_all.8``, ``%pmax.7``).
    assert all(l.startswith("%all-to-all.") for l in a2a)
    verdict = [l for l in lines if " all-reduce(" in l and "route.ids" in l
               and "branch_" not in l]
    assert len(verdict) == 1 and verdict[0].startswith("%all-reduce.")
    leaf = ".rows" if op == "pull" else ".grads"
    assert sorted("ids" if "route.ids" in l else leaf for l in a2a
                  if "branch_1_fun" in l) == sorted(["ids", leaf])
    # What is left of the gathered body is in the other branch.
    assert all("branch_0_fun" in l for l in lines
               if " all-gather(" in l or " all-gather-start(" in l)
    held = rps * dim * 4 + (rps * 4 if op == "push_row_adagrad" else 0)
    mem = compiled.memory_analysis()
    # The overflow count (``s32[1]`` a chip, a tile of its own in HBM) is
    # donated too: a launch allocates nothing for it.
    count_bytes = mem.alias_size_in_bytes - (0 if op == "pull" else held)
    assert 4 <= count_bytes <= 4096
    if op != "pull":
        # No second table anywhere: every table-sized result is a kernel's
        # in place, a parameter, or the conditional handing it on.
        whole = [l for l in lines if re.search(
            rf"= \(?f32\[{rps},{dim}\]", l)]
        assert whole and not [l for l in whole if " copy(" in l
                              or " scatter(" in l], whole


@pytest.mark.parametrize("handle", ["sum", "row_adagrad"])
def test_a_lane_packed_tables_pushes_at_full_size_write_it_with_row_add(
        v5e_chip, handle):
    """Both pushes of the configuration ``dlrm-terabyte-emb64`` (54,000,000
    rows of 64 f32 lanes kept two to a 128-lane physical row, 53,248
    lookups) lowered for the v5e: the physical table's one result is the
    ``row_add`` kernel's, in place over the donated table, the rows are
    placed and merged by physical row under ``ps.sparse.pack.place``
    before it, and the whole fits the chip.  The cell runs the sum; the
    push under the handle is measured by no cell and held here."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel import sparse

    rows, dim, lookups = 54_000_000, 64, 53_248
    pack = 128 // dim
    phys = rows // pack

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(v5e_chip, spec))

    store = sds((phys, 128), jnp.float32, P("kv", None))
    idx = sds((1, lookups), jnp.int32, P("kv", None))
    grads = sds((1, lookups, dim), jnp.float32, P("kv", None, None))
    if handle == "sum":
        body = lambda st, ix, g: sparse._scatter_rows(
            "kv", 1, rows, pack, dim, st, ix, g)
        specs = (P("kv", None), P("kv", None), P("kv", None, None))
        out_specs, donate, args = P("kv", None), (0,), (store, idx, grads)
        state = phys * 128 * 4
    else:
        body = lambda st, ac, ix, g, lr, eps: sparse._adagrad_sparse(
            "kv", 1, rows, pack, dim, st, ac, ix, g, lr, eps)
        specs = (P("kv", None), P("kv"), P("kv", None), P("kv", None, None),
                 P(), P())
        out_specs, donate = (P("kv", None), P("kv")), (0, 1)
        scalar = sds((), jnp.float32, P())
        args = (store, sds((rows,), jnp.float32, P("kv")), idx, grads,
                scalar, scalar)
        state = phys * 128 * 4 + rows * 4
    compiled = jax.jit(jax.shard_map(
        body, mesh=v5e_chip, in_specs=specs, out_specs=out_specs,
        check_vma=False), donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    table = [l for l in text.splitlines()
             if f"= f32[{phys},128]" in l and " parameter(" not in l]
    assert len(table) == 1, table
    assert " %row_add" in table[0] and "tpu_custom_call" in table[0]
    assert "ps.sparse.push.scatter_add" in table[0]
    assert "ps.sparse.pack.place/sort" in text
    assert ("ps.sparse.combine/sort" in text) == (handle == "row_adagrad")
    # The combine by physical row (128 lanes) is the kernel's, in the sum
    # under the combine's scope; under the handle it is the merge before
    # ``row_add``, and the first combine, by logical row of 64 lanes, keeps
    # XLA's scatter-add.
    _segment_sum_is_the_kernel(
        text, lookups,
        "ps.sparse.combine/ps.sparse.pack.place" if handle == "sum"
        else "ps.sparse.push.scatter_add/cond/branch_0_fun/"
             "ps.sparse.pack.place")
    assert bool([l for l in text.splitlines() if " scatter(" in l
                 and f"= f32[{lookups},{dim}]" in l]) == (
                     handle == "row_adagrad")
    mem = compiled.memory_analysis()
    assert state <= mem.alias_size_in_bytes < state + (1 << 20)
    # The batch's workspaces (27 MB each) fit the chip's own memory: none
    # of the batch's size is left in HBM.
    assert mem.temp_size_in_bytes < 1 << 20
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16e9


# -- the pull's result as the program leaves it (parallel/sparse.py) -----------


def _engine_on_described_chips(mesh, tables):
    """A ``SparseEngine`` over chips that are described and not attached,
    its tables registered by shape alone (nothing can be placed on them):
    the engine's OWN programs can then be built and compiled."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.sparse import SparseEngine, SparseTable

    eng = SparseEngine(mesh)
    S = eng.num_shards
    for name, (rps, dim) in tables.items():
        pack = 128 // dim
        table = SparseTable(name, S * rps, dim, rps, jnp.float32, pack=pack)
        eng._tables[name] = table
        eng._stores[name] = jax.ShapeDtypeStruct(
            (table.phys_rows * S, pack * dim), jnp.float32,
            sharding=NamedSharding(mesh, P("kv", None)))
    return eng


def _moves_of_a_batch(text, n, dim):
    """The compiled instructions that write a worker's batch over again: a
    copy, or a transpose that is none of the gather's own (``dimensions=
    {0,1}``: the identity, inside its fusion), with the batch for result."""
    import re

    batch = re.compile(
        rf"= f32\[(1,)?{n},{dim}\]\S* (copy|copy-start|transpose)\(")
    return [l.strip() for l in text.splitlines() if batch.search(l)
            and "dimensions={0,1}" not in l]


PULLS = {
    # chips, the tables' widths, whether the program threads a count
    "one-chip": (1, [128], False),
    "four-chips-routed": (4, [128], True),
    "one-chip-lane-packed-64": (1, [64], False),
    "four-chips-group-128-and-64": (4, [128, 64], True),
    # (PR 53) a group's entries of one width share ONE result, bare
    "one-chip-group-three-64-one-result": (1, [64, 64, 64], False),
}


@pytest.mark.parametrize("case", list(PULLS))
def test_the_pull_program_hands_the_batch_over_as_it_gathered_it(
        v5e8_mesh, case):
    """The engine's own pull programs for the v5e (4,096 lookups a worker,
    2^20 rows a shard; ``benchmark/tests/test_compile_fullsize_sparse_pull.py``
    compiles the cells' sizes): the result is ``[W, n, d]``, a worker's batch
    a chip, and after the gather (routed: after the rows' ``all-to-all`` and
    the permutation back to the batch's order) nothing of the batch's size is
    written again: the leading unit dimension is a bitcast, so the reshape
    that used to follow in a program of its own, and its copy, are gone and
    none has come in its place.  A 64-wide result is laid with the batch
    along the lanes by the compiler's own choice, before this change as
    after it: that one re-laying copy is the program's last instruction (of
    each branch where the exchange is routed) and the only one.  A group's
    entries of one width lie side by side in one result, ``[W, sum n, d]``
    (PR 53): three 64-wide tables end in ONE re-laying copy, not three."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    S, dims, routed = PULLS[case]
    rps, n = 1 << 20, 4096
    mesh = Mesh(np.array(list(v5e8_mesh.devices.flat[:S])), ("kv",))
    names = [f"t{d}" for d in dims]
    eng = _engine_on_described_chips(mesh, {nm: (rps, d)
                                            for nm, d in zip(names, dims)})
    assert eng._routed(n) == routed
    idx = jax.ShapeDtypeStruct((S, n), jnp.int32,
                               sharding=NamedSharding(mesh, P("kv", None)))
    count = [jax.ShapeDtypeStruct((S,), jnp.int32,
                                  sharding=NamedSharding(mesh, P("kv")))
             ] * routed
    stores = [eng._stores[nm] for nm in names]
    if len(names) == 1:
        prog = eng._sparse_program("pull", eng._tables[names[0]], n)
    else:
        prog = eng._sparse_group_program(
            "pull", [eng._tables[nm] for nm in names], (n,) * len(names))
    lowered = prog.lower(*stores, *[idx] * len(names), *count)
    outs = jax.tree_util.tree_leaves(lowered.out_info)
    # A result a width, in the order a width first appears.
    classes = {d: n * dims.count(d) for d in dims}
    assert [tuple(o.shape) for o in outs] == (
        [(S, m, d) for d, m in classes.items()] + [(S,)] * routed)
    compiled = lowered.compile()
    for sharding in jax.tree_util.tree_leaves(
            compiled.output_shardings)[:len(classes)]:
        assert sharding.is_equivalent_to(
            NamedSharding(mesh, P("kv", None, None)), 3)
    text = compiled.as_text()
    for d, m in classes.items():
        moves = _moves_of_a_batch(text, m, d)
        if d == 128:
            assert not moves, moves
            continue
        # The one re-laying of a narrow result, a branch of the routed
        # program's conditional its own: last, and of the layout.
        assert len(moves) == 1 + routed, moves
        assert all(" copy(" in mv and f"f32[1,{m},{d}]{{1,2,0:" in mv
                   for mv in moves), moves
        assert routed or moves[0].startswith("ROOT ")
    if dims == [128] and not routed:
        # The program's last instruction: the unit dimension, for nothing.
        root = [l.strip() for l in text[text.index("\nENTRY "):].splitlines()
                if l.strip().startswith("ROOT ")][0]
        assert f"f32[1,{n},128]" in root and " bitcast(" in root, root
    mem = compiled.memory_analysis()
    want = sum(n * d * 4 for d in dims)
    assert want <= mem.output_size_in_bytes <= want + 4096 * (1 + len(classes))


# -- LAMB's one pass and its pulled values (ops/fused_update.py) ----------------


def _lamb_program(devices, lens, op, dtype="float32", flags=None,
                  job_dtype=None):
    """(compiled, lowered text, total, padded) of the program of a bucket
    with ``lens`` under ``lamb`` over ``devices`` (described, not
    attached: the record alone, since registering would allocate).  With
    ``job_dtype`` the bucket is mixed and its gradient the job's rows."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import (CollectiveEngine, DenseBucket,
                                            _padded_len)

    handle = "lamb:1e-4,0.9,0.999,1e-6,0.01"
    chips = len(devices)
    mesh = Mesh(np.array(devices), ("kv",))
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    assert not eng._interpret
    lens = np.asarray(lens, dtype=np.int64)
    total = int(lens.sum())
    padded = _padded_len(total, chips, True)
    dtype = jnp.dtype(dtype)
    bucket = DenseBucket(
        name="tree", keys=np.arange(len(lens), dtype=np.uint64), val_len=0,
        dtype=dtype, total_len=total, padded_len=padded, lens=lens,
        flags=(np.zeros(len(lens), np.int32) if flags is None else flags),
        job_dtype=job_dtype)
    shard = NamedSharding(mesh, P("kv"))
    vec = jax.ShapeDtypeStruct((padded,), dtype, sharding=shard)
    slot = jax.ShapeDtypeStruct((chips,), jnp.float32, sharding=shard)
    grads = jax.ShapeDtypeStruct(
        (chips, total), bucket.job_dtype,
        sharding=NamedSharding(mesh, P("kv", None)))
    lowered = eng._program(op, padded, dtype, handle, bucket).lower(
        vec, vec, vec, slot, grads)
    return lowered.compile(), lowered.as_text(), total, padded


def _makers(text, shape):
    """The opcodes of the operations of a compiled text whose first result
    is ``shape``, parameters apart."""
    import re

    found = re.findall(
        rf"^\s*(?:ROOT )?%[\w.\-]+ = \(?{re.escape(shape)}[{{,)\s]\S* "
        rf"([\w\-]+)\(", text, flags=re.M)
    return [opcode for opcode in found if opcode != "parameter"]


def _results(text, kernel):
    """The result shapes (``f32[2627072,128]``, layouts dropped) of the
    custom call ``%<kernel>.1`` in a compiled text; none where the program
    has no such kernel."""
    import re

    line = [l for l in text.splitlines()
            if l.lstrip().startswith(f"%{kernel}.1 = ")]
    assert len(line) <= 1, line
    if not line:
        return []
    return re.findall(r"\w+\[[\d,]*\]",
                      line[0].split(" = ", 1)[1].split(" custom-call(")[0])


def _bert_large_lens():
    """The 398 tensors of ``bert-large-lamb`` and their flags, as the
    cell's driver registers them."""
    import fnmatch
    import json
    import sys

    from pslite_tpu.parallel.engine import KEY_NO_ADAPT, KEY_NO_DECAY

    bench = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark")
    sys.path.insert(0, bench)
    import buckets

    with open(os.path.join(bench, "configs", "bert-large-lamb.json")) as fh:
        config = json.load(fh)
    tensors = buckets.expand_tensors(config["tensors"])
    flags = np.array([
        (KEY_NO_DECAY | KEY_NO_ADAPT)
        if any(fnmatch.fnmatchcase(name, p)
               for p in config["no_decay_no_adapt"]) else 0
        for name, _ in tensors], dtype=np.int32)
    return np.array([n for _, n in tensors], dtype=np.int64), flags


def test_lamb_apply_writes_the_pulled_tree_at_full_size_on_one_chip(
        v5e8_mesh):
    """The program of ``bert-large-lamb.tree`` (what
    ``benchmark/tests/test_compile_fullsize_lamb_one_pass.py`` compiles):
    one shard holds the bucket, so every key but ``emb.word`` is held in
    VMEM and updated in one pass by ``lamb_apply``, which has the store, m
    and v for results and last a vector of the tree's own length that ends
    in the middle of the last tile: the program's pulled result as it
    stands; nothing copies the tree.  ``lamb_moments`` walks ``emb.word``'s
    477 tiles."""
    lens, flags = _bert_large_lens()
    compiled, lowered, total, padded = _lamb_program(
        v5e8_mesh.devices.flat[:1], lens, "push_pull_st", flags=flags)
    assert (total, padded) == (336226108, 5131 * 65536)
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    rows = padded // 128
    state = f"f32[{rows},128]"
    assert _results(text, "lamb_moments") == [state, state, "f32[796]"]
    assert "s32[477]" in text                     # the tiles it walks
    assert _results(text, "lamb_apply") == [state] * 3 + [f"f32[{total}]"]
    assert "ps.update.lamb.apply" in text
    made = _makers(text, f"f32[{total}]")
    assert made and set(made) <= {"get-tuple-element", "bitcast"}, made
    assert "all-gather" not in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * 4 * padded
    assert mem.temp_size_in_bytes < 10**7
    # Held: p, m, v, the gradient, and the pulled tree once.
    args = 3 * 4 * padded + 4 * total + 4
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert args + 4 * total <= held < args + 4 * total + 10**7


@pytest.mark.parametrize("op", ["push_pull_st", "push_st"])
def test_lamb_elsewhere_at_full_size_has_the_one_result_kernel(
        v5e8_mesh, op):
    """Over four shards the pulled tree is the all-gather of the shards,
    cut at the tree's length, and ``lamb_apply`` is the second of two
    passes with the store for its one result; a push alone on one chip
    returns no vector: the store, m and v."""
    lens, flags = _bert_large_lens()
    chips = 4 if op == "push_pull_st" else 1
    compiled, lowered, total, padded = _lamb_program(
        v5e8_mesh.devices.flat[:chips], lens, op, flags=flags)
    assert lowered.count("tpu_custom_call") == 2
    text = compiled.as_text()
    assert _results(text, "lamb_apply") == [
        f"f32[{padded // chips // 128},128]"] * (1 if chips == 4 else 3)
    made = _makers(text, f"f32[{total}]")
    if chips == 4:
        assert "all-gather" in text
        assert set(made) & {"slice", "fusion", "copy"}, made
    else:
        assert not made


@pytest.mark.parametrize("lens, dtype, one_pass", [
    ([2 * 65536 - 1000, 1000], "float32", True),   # ends on a tile's border
    ([300, 700], "float32", True),                 # shorter than a tile
    ([300, 513 - 300], "float32", True),           # the shortest it takes
    ([30522, 100000], "bfloat16", True),
    # a key VMEM cannot hold between two it can: both kernels, 130 tiles
    # walked by the first
    ([70000, 129 * 65536 + 5, 70000], "float32", True),
    ([70000, 129 * 65536 + 5, 70000], "bfloat16", True),
    # ... between two that lie in its first tile and its last: every tile
    # is walked, no key held, the second of two passes
    ([300, 129 * 65536 + 5, 1000], "float32", False),
    ([300, 129 * 65536 + 5, 1000], "bfloat16", False),
])
def test_lamb_apply_leaves_the_pulled_vector(v5e8_mesh, lens, dtype,
                                             one_pass):
    compiled, _, total, padded = _lamb_program(
        v5e8_mesh.devices.flat[:1], lens, "push_pull_st", dtype)
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    text = compiled.as_text()
    made = _makers(text, f"{short}[{total}]")
    assert made and set(made) <= {"get-tuple-element", "bitcast"}, made
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 16
    state = f"{short}[{padded // 128},128]"
    assert _results(text, "lamb_apply") == [state] * (3 if one_pass else 1) \
        + [f"{short}[{total}]"]
    assert _results(text, "lamb_moments") == (
        [state, state, f"f32[{2 * len(lens)}]"] if max(lens) > 128 * 65536
        else [])


@pytest.mark.parametrize("lens", [
    [30522, 100000],            # a ragged last block, in and out
    [2 * 65536 - 1000, 1000],   # ends on a tile's border
    [300, 213],                 # the shortest vector the kernels take
])
def test_the_lamb_kernels_take_the_jobs_bf16_over_an_f32_store(v5e8_mesh,
                                                               lens):
    """A mixed bucket on one chip, every key held: ``lamb_apply``, the
    program's one kernel, reads the job's bf16 row as the program was
    handed it and leaves the pulled bf16 vector last; nothing widens,
    narrows or copies outside it."""
    compiled, lowered, total, padded = _lamb_program(
        v5e8_mesh.devices.flat[:1], lens, "push_pull_st",
        job_dtype="bfloat16")
    assert lowered.count("tpu_custom_call") == 1
    text = compiled.as_text()
    rows = padded // 128
    # The row as the chip holds it (tiles of two rows, one of them
    # padding: the f32 row's bytes), the pulled vector packed.
    assert f"bf16[1,{total}]{{1,0:T(2,128)(2,1)}}" in text
    assert f"bf16[{total}]{{0:T(1024)(128)(2,1)}}" in text
    assert _results(text, "lamb_apply") == [f"f32[{rows},128]"] * 3 + [
        f"bf16[{total}]"]
    made = _makers(text, f"bf16[{total}]")
    assert made and set(made) <= {"get-tuple-element", "bitcast"}, made
    # (XLA prefetches a gradient of a few hundred KB into VMEM: a
    # copy-start / copy-done of its own, no pass over HBM.)
    assert set(_makers(text, f"bf16[1,{total}]")) <= {"copy-start",
                                                       "copy-done"}
    # (Where the keys end on a tile's border the store is as long.)
    assert set(_makers(text, f"f32[{total}]")) <= {"bitcast"}
    assert not _makers(text, f"f32[1,{total}]")
    assert "convert" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 16
    assert mem.argument_size_in_bytes < 4 * 4 * padded + 4096


@pytest.mark.parametrize("op, chips", [("push_st", 1), ("push_pull_st", 4)])
def test_a_mixed_bucket_elsewhere_has_the_one_result_kernel(v5e8_mesh, op,
                                                            chips):
    """A push alone returns nothing to round (on one chip the one pass's
    store, m and v); over four shards XLA widens
    the gradient before the f32 sum (``ps.push.widen``) and rounds the
    shards before the gather (``ps.pull.narrow``), which carries bf16."""
    import re

    compiled, lowered, total, padded = _lamb_program(
        v5e8_mesh.devices.flat[:chips], [30522, 100000, 65536 * 4], op,
        job_dtype="bfloat16")
    assert lowered.count("tpu_custom_call") == (1 if chips == 1 else 2)
    text = compiled.as_text()
    assert _results(text, "lamb_apply") == [
        f"f32[{padded // chips // 128},128]"] * (3 if chips == 1 else 1)
    if chips == 1:
        assert not _makers(text, f"bf16[{total}]")
        assert "convert" not in text
    else:
        assert re.search(rf"= bf16\[{padded}\]\S* all-gather\(", text)
        assert "ps.push.widen" in text and "ps.pull.narrow" in text
        assert re.search(
            r"= f32\[\d+(,\d+)?\]\S* (all-reduce|reduce-scatter)\(", text)


def test_a_mixed_bucket_of_up_to_512_values_leaves_the_rounding_to_xla(
        v5e8_mesh):
    """A vector of 512 values lies in one tile of its own length
    (``T(512)(128)(2,1)``), which Mosaic refuses as rank-1 blocks of
    ``lamb_apply``: it reads the bf16 row all the same, and the pulled
    values are the store's cut, rounded, after it."""
    compiled, _, total, padded = _lamb_program(
        v5e8_mesh.devices.flat[:1], [300, 212], "push_pull_st",
        job_dtype="bfloat16")
    text = compiled.as_text()
    assert _results(text, "lamb_apply") == [f"f32[{padded // 128},128]"] * 3
    assert set(_makers(text, f"bf16[{total}]")) & {"slice", "fusion",
                                                    "convert"}
    assert f"bf16[1,{total}]{{1,0:T(2,128)(2,1)" in text
    assert not _makers(text, f"f32[1,{total}]")


def test_the_elementwise_kernels_take_a_gradient_narrower_than_the_state(
        v5e8_mesh):
    """``adam`` on a mixed bucket with ``lens``: ``_elementwise_call``
    tiles for the narrower operand and widens in VMEM."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.parallel.engine import (CollectiveEngine, DenseBucket,
                                            _padded_len)

    handle = "adam:1e-4,0.9,0.999,1e-8"
    mesh = Mesh(np.array(v5e8_mesh.devices.flat[:1]), ("kv",))
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    lens = np.array([30522, 100000], dtype=np.int64)
    total, padded = int(lens.sum()), _padded_len(int(lens.sum()), 1, True)
    bucket = DenseBucket(
        name="tree", keys=np.arange(2, dtype=np.uint64), val_len=0,
        dtype=jnp.float32, total_len=total, padded_len=padded, lens=lens,
        flags=np.zeros(2, np.int32), job_dtype=jnp.bfloat16)
    shard = NamedSharding(mesh, P("kv"))
    vec = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=shard)
    slot = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=shard)
    grads = jax.ShapeDtypeStruct(
        (1, total), jnp.bfloat16, sharding=NamedSharding(mesh, P("kv", None)))
    compiled = eng._program("push_pull_st", padded, jnp.float32, handle,
                            bucket).lower(vec, vec, vec, slot,
                                          grads).compile()
    text = compiled.as_text()
    assert "%adam_update.1 = (f32[" in text
    assert _makers(text, f"bf16[{total}]")      # the pulled tree, rounded


def test_a_vector_the_chip_lays_in_one_tile_is_cut_from_the_store(
        v5e8_mesh):
    """Up to 512 values XLA lays a vector out in a single tile of its own
    length, which Mosaic refuses as a result of whole 1,024-element
    blocks (``fused_update.lamb_apply_pulls``): the program keeps the
    cut, and compiles."""
    compiled, _, total, padded = _lamb_program(
        v5e8_mesh.devices.flat[:1], [300, 212], "push_pull_st")
    text = compiled.as_text()
    assert _results(text, "lamb_apply") == [f"f32[{padded // 128},128]"] * 3
    assert _makers(text, f"f32[{total}]")


@pytest.mark.parametrize("op, pulls", [("push_pull_st", True),
                                       ("push_st", False)])
def test_muon_writes_a_key_back_where_it_lies_with_kernels(
        v5e8_mesh, op, pulls, tmp_path, monkeypatch):
    """``muon`` on a bucket of the cell's widths, every second key 512
    values off a tile of the store (behind a gain of 512 values, as every
    second layer of ``moonlight-16b-muon`` lies): wide and tall expert
    matrices, a key too wide for more than sixteen rows a block, an AdamW
    key of two grid steps whose m and v lie 512 off a tile too.  The
    kernels that write the keys back (``ops/muon.py`` ``row_apply``,
    ``row_adamw``: their own DMAs on windows four sublanes into a tile)
    lower through Mosaic; nothing of the store's size is XLA's, no copy,
    slice, update or fusion; and where the program pulls, the pulled
    vector is a view of the last kernel's second result
    (``benchmark/tests/test_compile_fullsize_muon_apply.py`` has the cell's
    own program).  The pulling program is built as a process with a
    compile cache builds it: the step's trace is kept
    (``utils/compile_cache.py`` ``call_traced``), and a second program,
    which a new process would build, is made from the kept trace without
    running ``muon_update`` and compiles to the same text."""
    import re

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pslite_tpu.ops import muon
    from pslite_tpu.parallel.engine import (KEY_ELEMENTWISE,
                                            CollectiveEngine, DenseBucket,
                                            _padded_len)

    handle = "muon:1e-3,0.95,0.1,0.9,0.95,1e-8"
    shapes = np.array([(1, 512), (1408, 2048), (2048, 1408), (1408, 2048),
                       (1, 524288), (1, 512), (2048, 11264), (64, 2048)])
    adamw = shapes[:, 0] == 1
    lens = shapes[:, 0] * shapes[:, 1]
    starts = np.concatenate([[0], np.cumsum(lens)])
    assert {int(s) % 1024 for s in starts[:-1]} == {0, 512}
    mesh = Mesh(np.array(v5e8_mesh.devices.flat[:1]), ("kv",))
    eng = CollectiveEngine(mesh=mesh, server_handle=handle)
    total, padded = int(lens.sum()), _padded_len(int(lens.sum()), 1, True)
    bucket = DenseBucket(
        name="tree", keys=np.arange(len(lens), dtype=np.uint64), val_len=0,
        dtype=jnp.float32, total_len=total, padded_len=padded, lens=lens,
        flags=np.where(adamw, KEY_ELEMENTWISE, 0).astype(np.int32),
        shapes=shapes)
    plan = eng._muon_plan(bucket)
    assert plan.pulls and len(plan.apply_keys) == len(lens)
    assert eng._kernel_pulls(op, handle, bucket) == pulls
    shard = NamedSharding(mesh, P("kv"))
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                             sharding=shard)
    state = [sds(s) for s in muon.state_shapes(plan)] + [sds((1,))]
    grads = jax.ShapeDtypeStruct(
        (1, total), jnp.float32, sharding=NamedSharding(mesh, P("kv", None)))
    from pslite_tpu.utils import compile_cache

    if pulls:
        monkeypatch.setattr(compile_cache, "_trace_dir",
                            lambda: str(tmp_path))

    def compile_():
        eng = CollectiveEngine(mesh=mesh, server_handle=handle)
        return eng._program(op, padded, jnp.float32, handle, bucket).lower(
            sds((padded,)), *state, grads).compile()

    compiled = compile_()
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        4 * padded + plan.state_bytes)
    text = compiled.as_text()
    kept = [f for f in os.listdir(tmp_path) if f.startswith("traced-")]
    assert len(kept) == pulls
    if pulls:
        compile_cache._traced.clear()       # what a new process knows
        monkeypatch.setattr(muon, "muon_update", None)
        again = compile_().as_text()
        ops = lambda t: [re.sub(r"metadata=\{[^}]*\}", "", l)  # call sites
                         for l in t[t.index("ENTRY"):].splitlines()
                         if "tpu_custom_call" not in l]     # and kernels'
        assert ops(again) == ops(text)
        assert again.count("tpu_custom_call") == text.count(
            "tpu_custom_call")
    lines = [l.replace("ROOT ", "").strip()
             for l in text[text.index("ENTRY"):].splitlines()]
    applies = [l for l in lines if l.startswith("%muon_row_apply")]
    adamws = [l for l in lines if l.startswith("%muon_row_adamw")]
    # Three chunks, one of both orientations; three AdamW keys.
    assert len(applies) == 4 and len(adamws) == 3
    store, pulled = f"f32[{padded // 128},128]", f"f32[{total // 128},128]"
    for l in applies + adamws:
        # The store first, an f32 result: ``benchmark/muon_ops.py``.
        assert "tpu_custom_call" in l and re.search(
            rf"= \(?{re.escape(store)}", l), l[:200]
        assert (pulled in l.split(" custom-call(")[0]) == pulls, l[:200]
    whole = [l for l in lines
             if re.search(rf"= \(?f32\[({padded}|{total}|{padded // 128},128"
                          rf"|{total // 128},128)\]", l)]
    xlas = [l[:160] for l in whole if not re.search(
        r" (parameter|bitcast|get-tuple-element|custom-call|opt-barrier|"
        r"tuple)\(", l)]
    assert whole and not xlas, xlas
    if pulls:
        root = next(l for l in lines if l.startswith("%tuple")
                    or " tuple(" in l and f"f32[{total}]" in l)
        last = re.findall(r"%([\w.\-]+)", root.split(" tuple(")[1])[-1]
        made = next(l for l in lines if l.startswith(f"%{last} = "))
        assert " bitcast(" in made, made[:200]
