"""Child for the crash-consistent reshard test: worker rank 1 DIES
before calling reshard; worker rank 0 must time out at the entry
barrier and abort with its engine untouched (old mesh, stores intact).
See vans/ici_van.py reshard_engines CRASH SEMANTICS."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import pslite_tpu as ps  # noqa: E402


def main() -> None:
    role = os.environ["DMLC_ROLE"]
    ps.start_ps()
    if role == "worker":
        rank = int(os.environ["DMLC_RANK"])
        kv = ps.KVWorker(0, 0)
        eng = kv.engine
        keys = np.arange(4, dtype=np.uint64)
        val_len = 8
        kv.register_dense("g", keys, val_len)
        vals = np.full(4 * val_len, float(rank + 1), np.float32)
        outs = np.zeros_like(vals)
        kv.wait(kv.push_pull(keys, vals, outs))
        np.testing.assert_allclose(outs, 12.0)

        mode = os.environ.get("PS_CRASH_MODE", "exit_before")
        if rank == 1 and mode == "exit_before":
            # DIE before the coordinated reshard: no barrier request
            # ever reaches the scheduler from this worker.
            sys.stdout.flush()
            os._exit(42)
        if rank == 1 and mode == "stage_fail":
            # Fail rank 1's STAGING (at the first new-mesh placement —
            # AFTER the collective snapshot legs both ranks run, so the
            # survivor reaches the commit barrier rather than a jax
            # collective): rank 1 must raise fast and go SILENT, never
            # releasing the survivors' commit barrier with a stray
            # resume request.
            from pslite_tpu.parallel import placement

            real = placement.place_host_array

            def fail_first(*a, **kw):
                placement.place_host_array = real
                raise RuntimeError("injected staging failure")

            placement.place_host_array = fail_first

        from jax.sharding import Mesh

        devs = sorted(jax.devices(),
                      key=lambda d: (d.process_index, d.id))
        mesh4 = Mesh(np.array(devs[0:2] + devs[4:6]), ("kv",))
        old_padded = eng.bucket("g").padded_len
        try:
            kv.reshard(mesh4)  # PS_RESHARD_TMO_S set by the parent
            print("CRASH_FAIL reshard succeeded with a dead peer",
                  flush=True)
        except Exception as exc:  # noqa: BLE001 - the expected abort
            ok = (
                eng.num_shards == 8
                and eng.bucket("g").padded_len == old_padded
            )
            # Local shards must still hold the pre-crash state (12.0
            # everywhere) — reads of addressable shards are local.
            for s in eng._stores["g"].addressable_shards:
                ok = ok and np.allclose(np.asarray(s.data), 12.0)
            print(f"CRASH_OK rank={rank} untouched={ok} "
                  f"{type(exc).__name__}", flush=True)
        # Skip finalize: the cluster is degraded by design (dead peer);
        # finalize's ALL_GROUP barrier would wedge.
        sys.stdout.flush()
        os._exit(0)
    ps.finalize()
    print(f"{role} DONE", flush=True)


if __name__ == "__main__":
    main()
