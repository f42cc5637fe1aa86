"""Child process for the multi-process IciTcpVan test.

Each worker process pins 4 virtual CPU devices, bootstraps over the TCP
control plane, joins jax.distributed (coordinator derived from the DMLC
env), and drives a dense push_pull over the GLOBAL 8-device mesh.
The platform pin must NOT touch the backend before jax.distributed
initializes, so this sets env + config directly instead of pin_cpu().
"""

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
        assert eng is not None, "ici_tcp worker has no engine"
        assert eng.num_shards == 8, (
            f"expected global 8-device mesh, got {eng.num_shards}"
        )
        assert jax.process_count() == 2, jax.process_count()

        keys = np.arange(4, dtype=np.uint64)
        val_len = 8
        kv.register_dense("g", keys, val_len)
        # Worker r contributes (r+1) broadcast to its 4 local mesh rows:
        # aggregated sum = 4*1 + 4*2 = 12 on every element.
        vals = np.full(4 * val_len, float(rank + 1), np.float32)
        outs = np.zeros_like(vals)
        kv.wait(kv.push_pull(keys, vals, outs))
        np.testing.assert_allclose(outs, 12.0)

        # Second round on the same bucket: store accumulated 12s,
        # push adds another 12 -> 24 (server aggregation contract,
        # kv_app.h:430-452, across 2 processes x 4 shards).
        kv.wait(kv.push_pull(keys, vals, outs))
        np.testing.assert_allclose(outs, 24.0)

        # Sparse table across processes: every worker row pushes 1.0
        # into row 3; 8 mesh rows total -> store[3] = 8 per dim.
        eng_sp = kv.po.van.sparse_engine
        eng_sp.register_sparse("emb", num_rows=16, dim=4)
        idx = np.full((4, 1), 3, np.int32)  # this process's 4 rows
        g = np.ones((4, 1, 4), np.float32)
        kv.wait(kv.push_sparse("emb", idx, g))
        out_sp = np.zeros((4, 1, 4), np.float32)
        kv.wait(kv.pull_sparse("emb", idx, out=out_sp))
        np.testing.assert_allclose(out_sp, 8.0)

        # Coordinated elastic recut over the LIVE cluster: both
        # worker processes call kv.reshard with the same 4-device
        # mesh (2 from each process); barriers ride the real TCP
        # control plane, the collective snapshot rides
        # jax.distributed.  State must survive and training continue
        # on the new fan-in.
        from jax.sharding import Mesh

        devs = sorted(jax.devices(),
                      key=lambda d: (d.process_index, d.id))
        mesh4 = Mesh(np.array(devs[0:2] + devs[4:6]), ("kv",))
        kv.reshard(mesh4)
        assert eng.num_shards == 4, eng.num_shards
        out2 = np.zeros_like(vals)
        kv.wait(kv.pull(keys, out2))
        np.testing.assert_allclose(out2, 24.0)
        # Flat [total] broadcasts to my (now 2) local worker rows:
        # sum adds 2*1 + 2*2 = 6 on top of the carried 24.
        outs3 = np.zeros(4 * val_len, np.float32)
        kv.wait(kv.push_pull(keys, vals, outs3))
        np.testing.assert_allclose(outs3, 30.0)
        print(f"WORKER_OK {outs[0]}", flush=True)
    ps.finalize()
    print(f"{role} DONE", flush=True)


if __name__ == "__main__":
    main()
