"""KV push/pull basics — the ps-lite "hello world", any van.

Run a 2-worker cluster on one machine::

    python -m pslite_tpu.tracker.local -n 2 -s 2 -- python examples/kv_basics.py
    python -m pslite_tpu.tracker.local -n 2 -s 2 --van shm -- python examples/kv_basics.py

Under an ICI van the worker also registers a dense bucket, which then rides
the jitted collectives on the worker's devices while the scheduler and the
server stay off JAX.  One worker process per host there: every worker
process builds its mesh from all local devices, and a chip belongs to one
process at a time::

    python -m pslite_tpu.tracker.local -n 1 -s 1 --van ici_tcp -- python examples/kv_basics.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import pslite_tpu as ps


def main() -> None:
    role = os.environ.get("DMLC_ROLE")
    if role is None:
        sys.exit(
            "DMLC_ROLE not set — run this under the launcher:\n"
            "  python -m pslite_tpu.tracker.local -n 2 -s 2 -- "
            "python examples/kv_basics.py"
        )
    ps.start_ps()

    server = None
    if role in ("server", "joint"):
        server = ps.KVServer(0)
        server.set_request_handle(ps.KVServerDefaultHandle())

    if role in ("worker", "joint"):
        po = ps.postoffice(ps.Role.WORKER)
        kv = ps.KVWorker(0, 0)

        # One key per server, 1024 floats each.
        ranges = po.get_server_key_ranges()
        keys = np.sort(
            np.array([r.begin + 1 for r in ranges], dtype=np.uint64)
        )
        grads = np.full(len(keys) * 1024, 1.0, dtype=np.float32)

        ts = kv.push(keys, grads)          # async; returns a timestamp
        kv.wait(ts)                        # ZPush/Wait semantics
        po.barrier(0, ps.WORKER_GROUP)     # all workers pushed

        params = np.zeros_like(grads)
        kv.wait(kv.pull(keys, params))     # aggregated across workers
        expected = float(po.num_workers)
        print(f"worker {po.my_rank()}: pulled {params[0]} "
              f"(expected {expected})")
        assert np.allclose(params, expected)

        # Wire-compressed push for bandwidth-limited links:
        kv.wait(kv.push(keys, grads, compress="int8"))

        if kv.engine is not None:
            # ICI van: a registered bucket is one fused reduce-scatter +
            # server update + all-gather over this worker's devices.
            dense = np.arange(8, dtype=np.uint64) + 10_000
            kv.register_dense("dense", dense, val_len=1024)
            ones = np.ones(8 * 1024, dtype=np.float32)
            out = np.zeros_like(ones)
            kv.wait(kv.push_pull(dense, ones, out))
            devices = kv.engine.mesh.devices.flat
            print(f"worker {po.my_rank()}: registered bucket over "
                  f"{kv.engine.num_workers} {devices[0].platform} "
                  f"device(s) pulled {out[0]}")
            assert np.allclose(out, kv.engine.num_workers)

    ps.finalize()
    if server is not None:
        server.stop()


if __name__ == "__main__":
    main()
