"""The parity CLI and the loopback fixtures of ``pslite_tpu/benchmark.py``
stay runnable: one short leg of each ``--mode`` a document names, through
the launcher where the mode needs real sockets, and the storms other
tests compare on.  Counts and exactness are asserted; a wall clock here
is a CPU figure of the host plane and no statement about speed."""

import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")


def test_van_latency_harness():
    """The parity CLI through the launcher: a 1w+1s tcp cluster must
    yield a parseable us-per-key line."""
    import os
    import re

    cmd = [
        sys.executable, "-m", "pslite_tpu.tracker.local",
        "-n", "1", "-s", "1", "--van", "tcp", "--",
        sys.executable, "-m", "pslite_tpu.benchmark",
        "--len", "65536", "--repeat", "2", "--mode", "push_pull",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=300, cwd="/root/repo", env=env)
    assert out.returncode == 0, out.stderr[-1500:]
    lats = re.findall(r"avg latency ([0-9.]+) us/key", out.stdout)
    assert lats and float(lats[0]) > 0, out.stdout[-800:]


def test_telemetry_overhead_guard():
    """The telemetry layer must never silently become the bottleneck:
    the kv loopback storm with PS_TELEMETRY on — INCLUDING the
    continuous METRICS_PULL sampler at a 1 s interval
    (docs/observability.md) — stays within 10% of telemetry-off on the
    stub storm, and so does TAIL TRACING at the production floor rate
    (every request stamped and span-recorded, keep decided at
    completion).  Min-of-3 per leg to damp scheduler noise, plus a
    small absolute epsilon for sub-second walls."""
    from pslite_tpu.benchmark import kv_loopback_storm

    def best(telemetry: bool, extra=None) -> float:
        walls = []
        for _ in range(3):
            r = kv_loopback_storm(
                n_workers=2, n_servers=2, msgs_per_worker=40,
                keys_per_msg=8, val_len=512, telemetry=telemetry,
                env_extra=extra,
            )
            walls.append(r["wall_s"])
        return min(walls)

    # Interleave-insensitive order: off first warms every code path.
    off = best(False)
    on = best(True, {"PS_METRICS_INTERVAL": "1"})
    assert on <= off * 1.10 + 0.05, (
        f"telemetry overhead too high: on={on:.3f}s off={off:.3f}s "
        f"({on / off:.2f}x)"
    )
    tail = best(True, {"PS_TRACE_TAIL": "slow:p95,errors,floor:0.001"})
    assert tail <= off * 1.10 + 0.05, (
        f"tail-tracing overhead too high: tail={tail:.3f}s "
        f"off={off:.3f}s ({tail / off:.2f}x)"
    )
    # And the instrumented leg actually measured something.
    r = kv_loopback_storm(n_workers=1, n_servers=1, msgs_per_worker=5,
                          telemetry=True)
    tel = r["telemetry"]
    worker = next(v for k, v in tel.items() if k.startswith("worker"))
    assert worker["counters"]["kv.pushes"] == 5
    assert worker["histograms"]["kv.push_latency_s"]["count"] == 5


def test_chunk_hol_harness():
    """One subprocess leg of ``--mode chunk_hol`` (real tcp cluster via
    the local tracker) must produce the measurement line.  Ratios are
    asserted nowhere; see docs/chunking.md."""
    from pslite_tpu.benchmark import _chunk_run

    r = _chunk_run(8, 1, str(256 << 10))
    assert r["push_gbps"] > 0
    assert r["pull_p50_ms"] >= 0 and r["pull_p99_ms"] >= r["pull_p50_ms"]


def test_quantized_push_harness():
    """One subprocess leg of ``--mode quantized_push`` with a codec set
    (real tcp cluster via the local tracker) must produce the
    measurement line; goodput is defined over RAW bytes (effective
    goodput)."""
    from pslite_tpu.benchmark import _chunk_run

    r = _chunk_run(8, 1, str(256 << 10),
                   extra_env={"PS_BENCH_CODEC": "int8",
                              "PS_CODEC_EF": "0"},
                   mode="quantized_push")
    assert r["push_gbps"] > 0
    assert r["pull_p99_ms"] >= r["pull_p50_ms"] >= 0


@pytest.mark.slow
def test_small_op_storm_harness():
    """One short subprocess leg of ``--mode small_op_storm`` with the
    combiner on (real tcp cluster via the local tracker) must produce
    the measurement line with batches actually formed and the
    order-sensitive store check passing.  Slow-marked like the dlrm
    harness: the plane's semantics are covered by the fast loopback
    tests in tests/test_batching.py."""
    from pslite_tpu.benchmark import _small_op_run

    r = _small_op_run(1.0, batch=True)
    assert r["ops"] > 0 and r["msgs_per_s"] > 0
    assert r["ops_per_frame"] > 1.0  # multi-op frames really formed
    assert r["store_exact"]
    assert r["p99_ms"] >= r["p50_ms"] >= 0


@pytest.mark.slow
def test_serving_fanin_harness():
    """One short subprocess leg of ``--mode serving_fanin`` with the
    aggregation planes on (real 1w+2s tcp cluster via the local
    tracker) must produce the measurement line with the fan-in actually
    formed (response frames per request far below the fan-out) and
    every spot-checked request bit-exact.  Slow-marked like the
    small-op harness: the plane's semantics are covered by the fast
    loopback tests in tests/test_multi_get.py."""
    from pslite_tpu.benchmark import _serving_fanin_run

    r = _serving_fanin_run(1.0, batch=True)
    assert r["reqs"] > 0 and r["reqs_per_s"] > 0
    assert r["servers"] == 2
    # Fan-in really formed: ~1 frame per contacted server, nowhere
    # near one frame per lookup.
    assert r["frames_per_req"] < r["fanout"] / 4
    assert r["store_exact"]
    assert r["p99_ms"] >= r["p50_ms"] >= 0


def test_multi_tenant_admission_probe():
    """Tenant admission (docs/qos.md): the loopback flood sheds with
    OPT_OVERLOAD fast-fails, nothing hangs, store bit-exact at
    applied-count."""
    from pslite_tpu.benchmark import admission_probe

    r = admission_probe()
    assert r["applied"] + r["shed"] == r["offered"]
    assert r["shed"] > 0
    assert r["store_exact"]


@pytest.mark.slow
def test_dlrm_serve_harness():
    """One subprocess leg of ``--mode dlrm_serve`` with the hot cache
    on (real tcp cluster via the local tracker) must produce the
    measurement line with a nonzero hit rate and bit-exact spot checks.
    Slow-marked: the cache semantics are already covered by the fast
    loopback tests in tests/test_qos.py."""
    from pslite_tpu.benchmark import _dlrm_run

    r = _dlrm_run(150, cache=True)
    assert r["samples"] == 150
    assert r["hit_rate"] > 0.3
    assert r["pull_p50_ms"] >= 0


def test_send_lanes_fanout_harness():
    """Send lanes (docs/send_lanes.md): laned fan-out must beat the
    serialized (PS_SEND_LANES=0) replay on a stub transport with a
    fixed per-message delay."""
    from pslite_tpu.benchmark import fanout_wall_times

    laned, serial = fanout_wall_times(n_peers=6, delay_s=0.02, rounds=2)
    assert laned > 0 and serial > 0
    # Serial must cost ~6x the delay; laned ~1-2x.  Keep the bound loose
    # for CI noise but strictly below the no-overlap regime.
    assert laned < serial, (laned, serial)
    assert laned < 0.6 * serial, (laned, serial)
