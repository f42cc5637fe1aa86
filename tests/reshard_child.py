"""Child for the multi-process elastic reshard test.

Two jax.distributed processes x 4 virtual CPU devices.  The engine
starts on the full 8-device mesh, shrinks the kv axis to a 4-device
mesh spanning BOTH processes (2 devices each), grows back to 8 — state
(store + fused optimizer momentum + sparse table rows) must survive
every recut and continued training must aggregate on the new fan-in.
Reshard is a collective: both processes call it with the same mesh.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import faulthandler

faulthandler.dump_traceback_later(240, exit=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from pslite_tpu.parallel.engine import CollectiveEngine  # noqa: E402
from pslite_tpu.parallel.sparse import SparseEngine  # noqa: E402


def main() -> int:
    rank = int(os.environ["RESHARD_RANK"])
    coord = os.environ["RESHARD_COORD"]
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=2, process_id=rank
    )
    devices = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    assert len(devices) == 8, devices
    mesh8 = Mesh(np.array(devices), ("kv",))
    # The small mesh spans BOTH processes (2 devices each) so the
    # multi-process path is exercised on both sides of the recut.
    mesh4 = Mesh(np.array(devices[0:2] + devices[4:6]), ("kv",))

    eng = CollectiveEngine(mesh=mesh8, server_handle="sum")
    keys = np.arange(6, dtype=np.uint64)
    val_len = 100  # total 600: padding differs between 8 and 4 shards
    eng.register_dense("b", keys, val_len)
    assert eng._is_multiprocess()

    # 1-D multi-process host contract: rows = MY 4 local worker rows.
    g8 = np.full((4, 600), float(rank + 1), np.float32)
    out = np.asarray(eng.push_pull("b", g8))
    np.testing.assert_allclose(out, 12.0)  # 4*1 + 4*2

    # Momentum bucket: fused optimizer STATE must move with the recut.
    # lr=0.1, mu=0.9; step 1 from zero momentum: store = -0.1 * sum.
    eng.register_dense("m", keys, val_len)
    m1 = np.asarray(eng.push_pull("m", g8, handle="sgd_momentum:0.1,0.9"))
    np.testing.assert_allclose(m1, -0.1 * 12.0, rtol=1e-5)

    # Sparse table alongside (its own collective reshard): every one of
    # my 4 local worker rows pushes 1.0 into global row 3.
    se = SparseEngine(mesh8, "kv")
    se.register_sparse("emb", num_rows=16, dim=4)
    idx8 = np.full((4, 1), 3, np.int32)
    se.push("emb", idx8, np.ones((4, 1, 4), np.float32))
    se.block("emb")

    # ---- shrink: 8 -> 4 shards (both processes keep devices) ----------
    eng.reshard(mesh4)
    se.reshard(mesh4)
    assert eng.num_shards == 4 and se.num_shards == 4
    np.testing.assert_allclose(np.asarray(eng.pull("b")), 12.0)
    idx4 = np.full((2, 1), 3, np.int32)
    got = se.pull("emb", idx4)  # sharded per worker row: read MY shards
    for s in got.addressable_shards:
        np.testing.assert_allclose(np.asarray(s.data), 8.0)

    # Continued training on the new fan-in: my 2 local rows.
    g4 = np.full((2, 600), float(rank + 1), np.float32)
    out = np.asarray(eng.push_pull("b", g4))
    np.testing.assert_allclose(out, 12.0 + 6.0)  # + 2*1 + 2*2

    # Momentum recurrence continues across the recut: the carried
    # momentum (12) decays by mu and adds the new sum (6):
    # store = -1.2 - 0.1*(0.9*12 + 6) = -2.88.
    m2 = np.asarray(eng.push_pull("m", g4, handle="sgd_momentum:0.1,0.9"))
    np.testing.assert_allclose(m2, -0.1 * 12.0 - 0.1 * (0.9 * 12.0 + 6.0),
                               rtol=1e-5)

    # ---- grow: 4 -> 8 shards ------------------------------------------
    eng.reshard(mesh8)
    assert eng.num_shards == 8
    np.testing.assert_allclose(np.asarray(eng.pull("b")), 18.0)
    out = np.asarray(eng.push_pull("b", g8))
    np.testing.assert_allclose(out, 30.0)

    print(f"RESHARD_OK rank={rank}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
