"""Per-message event tracing (ENABLE_PROFILING), van byte counters."""

import numpy as np

from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker

from helpers import LoopbackCluster


def test_profiler_event_log_and_byte_counters(tmp_path):
    path = tmp_path / "trace.csv"
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1,
        env_extra={"ENABLE_PROFILING": "1", "PROFILE_PATH": str(path)},
    )
    cluster.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        worker = KVWorker(0, 0, postoffice=cluster.workers[0])
        keys = np.array([9], dtype=np.uint64)
        vals = np.ones(32, dtype=np.float32)
        worker.wait(worker.push(keys, vals))
        out = np.zeros_like(vals)
        worker.wait(worker.pull(keys, out))

        van = cluster.workers[0].van
        assert van.send_bytes > 0
        assert van.recv_bytes > 0
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()

    lines = path.read_text().strip().splitlines()
    # key,event_kind,timestamp_us — the reference's (key, event, µs) format.
    assert any(line.startswith("9,send_push,") for line in lines), lines
    assert any(line.startswith("9,recv_pull,") for line in lines), lines
    for line in lines:
        key, event, ts = line.split(",")
        assert event.split("_")[0] in ("send", "recv")
        assert int(ts) > 0


def test_engine_path_events_and_byte_counters(tmp_path):
    """The collective fast path has engine byte counters next to
    Van.send_bytes/recv_bytes and stamps every op's stages into the
    process's StageClock; ENABLE_PROFILING stays what the reference has,
    the van's per-message log, and holds no engine lines."""
    import pytest

    pytest.importorskip("jax")
    from pslite_tpu.utils.profiling import STAGES, stage_clock

    path = tmp_path / "engine_trace.csv"
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1, van_type="ici",
        env_extra={"ENABLE_PROFILING": "1", "PROFILE_PATH": str(path)},
    )
    cluster.start()
    try:
        worker = KVWorker(0, 0, postoffice=cluster.workers[0])
        keys = np.arange(2, dtype=np.uint64)
        val_len = 16
        worker.register_dense("prof", keys, val_len)
        vals = np.ones(2 * val_len, dtype=np.float32)
        outs = np.zeros_like(vals)
        before = stage_clock().totals()
        worker.wait(worker.push_pull(keys, vals, outs))
        worker.wait(worker.push(keys, vals))
        out = np.zeros_like(vals)
        worker.wait(worker.pull(keys, out))
        after = stage_clock().totals()

        eng = worker.engine
        payload = 2 * val_len * 4
        assert eng.push_bytes == 2 * payload  # push_pull + push
        assert eng.pull_bytes == 2 * payload  # push_pull + pull
        for stage in STAGES:
            assert after[stage][1] - before[stage][1] == 3, stage
            assert after[stage][0] >= before[stage][0]
    finally:
        cluster.finalize()

    assert "_engine," not in path.read_text()
