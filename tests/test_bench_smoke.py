"""bench.py must stay runnable: exercise its measurement helper on the CPU
mesh and check the JSON contract fields.

Tier-1 note: the canonical gate these tests ride under is pinned as
``make tier1`` (Makefile — the verbatim ROADMAP.md invocation), so the
builder and reviewer never drift apart on pytest flags."""

import json
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")


def test_measure_helper_runs():
    import bench
    from pslite_tpu.parallel.engine import CollectiveEngine

    eng = CollectiveEngine()
    wall, dev = bench._measure(
        eng, "smoke", num_keys=2, val_len=1024, iters=2
    )
    assert wall > 0
    assert dev is None  # CPU mesh: no TPU plane in the trace


def test_latency_samples_helper():
    """_latency_samples (full-mode-only path: the driver is otherwise
    its first executor) returns per-op wall latencies; no device mean
    on the CPU mesh."""
    import bench
    from pslite_tpu.parallel.engine import CollectiveEngine

    eng = CollectiveEngine()
    lats, dev_us = bench._latency_samples(eng, "lat_smoke", 2, 1024, 3)
    assert len(lats) == 3 and all(l > 0 for l in lats)
    assert dev_us is None
    p50, p99 = bench._pctls(lats)
    assert p50 <= p99


def test_van_latency_harness():
    """The van_latency section's exact harness (full-mode-only): a
    1w+1s tcp cluster through the launcher must yield a parseable
    us-per-key line."""
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


def test_recorder_retry_and_partial(tmp_path):
    """_Recorder.run retries a flapping section, records a persistent
    failure in sections_failed, and keeps the on-disk record valid."""
    import bench

    rec = bench._Recorder(str(tmp_path / "partial.json"))
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient flap")
        return {"ok_field": 1}

    assert rec.run("flaky", flaky, retries=1, retry_sleep_s=0.0)
    assert calls["n"] == 2

    def dead():
        raise RuntimeError("hard down")

    assert not rec.run("dead", dead, retries=1, retry_sleep_s=0.0)
    snap = json.loads((tmp_path / "partial.json").read_text())
    assert snap["ok_field"] == 1
    assert snap["sections_done"] == ["flaky"]
    assert snap["sections_failed"] == [
        {"section": "dead", "error": "RuntimeError: hard down"}
    ]


def test_bench_kill9_leaves_valid_partial(tmp_path):
    """VERDICT r04 ask #2 'done' criterion: kill -9 mid-run still yields
    a valid, SHA-stamped partial JSON on disk."""
    import os

    partial = tmp_path / "partial.json"
    env = dict(
        os.environ,
        PS_BENCH_QUICK="1",
        JAX_PLATFORMS="cpu",
        PS_BENCH_PARTIAL=str(partial),
    )
    proc = subprocess.Popen(
        [sys.executable, "bench.py"],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        cwd="/root/repo",
        env=env,
        text=True,
    )
    try:
        # Wait for the per-op sweep to COMPLETE (the replay_sweep mark
        # means per_op_sweep's fields were flushed), then SIGKILL.  The
        # stderr read runs on a helper thread so a silently hung child
        # fails the test at the deadline instead of blocking readline
        # forever.
        import threading

        hit = threading.Event()

        def _scan():
            for line in proc.stderr:
                if "replay_sweep" in line:
                    hit.set()
                    return

        t = threading.Thread(target=_scan, daemon=True)
        t.start()
        assert hit.wait(timeout=240), \
            "bench never reached the replay_sweep section"
    finally:
        proc.kill()
        proc.wait(timeout=30)
    snap = json.loads(partial.read_text())
    assert snap["git_sha"]
    assert snap["started_at"]
    assert "per_op_sweep" in snap["sections_done"]
    assert "sweep_1key_wall" in snap
    # The record says it is incomplete, not a finished measurement.
    assert snap["error"]


def test_bench_cli_contract(tmp_path):
    import os

    # PS_BENCH_QUICK=1 JAX_PLATFORMS=cpu is bench.py's explicit CPU
    # request (a full run refuses anything but a TPU).  The partial record
    # goes to a temp path: the repo-root default must stay reserved for
    # REAL bench runs (a stale quick-smoke partial there could be mistaken
    # for evidence).
    env = dict(
        os.environ,
        PS_BENCH_QUICK="1",
        JAX_PLATFORMS="cpu",
        PS_BENCH_PARTIAL=str(tmp_path / "partial.json"),
        # The multi_tenant, small_op_batching, serving_fanin,
        # replica_read, durable_store, and autopilot sections cost
        # real-process / elastic-cluster storms each and have their
        # own dedicated harness tests (admission probe, dlrm_serve,
        # test_qos.py, test_batching.py, test_multi_get.py,
        # test_replica_read.py, test_durability.py,
        # test_tiered_store.py, test_autopilot.py + the harness
        # smokes below) — keep the CLI-contract smoke inside the
        # tier-1 wall budget; the skip markers they record are
        # exactly what bench_diff treats as absent.
        PS_BENCH_SKIP="multi_tenant,small_op_batching,serving_fanin,"
                      "replica_read,durable_store,autopilot",
    )
    out = subprocess.run(
        [sys.executable, "bench.py"],
        capture_output=True,
        timeout=560,
        cwd="/root/repo",
        env=env,
    )
    assert out.returncode == 0, out.stderr.decode()[-1500:]
    lines = [l for l in out.stdout.decode().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    for field in ("metric", "value", "unit"):
        assert field in rec
    assert rec["value"] > 0
    assert "error" not in rec
    # The CPU request names its platform.
    assert rec["platform"] == "cpu"
    assert rec.get("multi_tenant_skipped") == "PS_BENCH_SKIP"
    assert rec.get("small_op_batching_skipped") == "PS_BENCH_SKIP"
    assert rec.get("serving_fanin_skipped") == "PS_BENCH_SKIP"
    assert rec.get("replica_read_skipped") == "PS_BENCH_SKIP"
    assert rec.get("durable_skipped") == "PS_BENCH_SKIP"
    assert rec.get("autopilot_skipped") == "PS_BENCH_SKIP"


def test_telemetry_overhead_guard():
    """The telemetry layer must never silently become the bottleneck:
    the kv loopback storm with PS_TELEMETRY on — INCLUDING the
    continuous METRICS_PULL sampler at a 1 s interval
    (docs/observability.md) — stays within 10% of telemetry-off on the
    stub bench, and so does TAIL TRACING at the production floor rate
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
    """The chunk_streaming section's harness: one subprocess leg of
    ``--mode chunk_hol`` (real tcp cluster via the local tracker) must
    produce the measurement line.  Ratios are asserted nowhere — the
    bench records them; see docs/chunking.md."""
    from pslite_tpu.benchmark import _chunk_run

    r = _chunk_run(8, 1, str(256 << 10))
    assert r["push_gbps"] > 0
    assert r["pull_p50_ms"] >= 0 and r["pull_p99_ms"] >= r["pull_p50_ms"]


def test_quantized_push_harness():
    """The quantized_push section's harness: one subprocess leg of
    ``--mode quantized_push`` with a codec set (real tcp cluster via
    the local tracker) must produce the measurement line; goodput is
    defined over RAW bytes (effective goodput)."""
    from pslite_tpu.benchmark import _chunk_run

    r = _chunk_run(8, 1, str(256 << 10),
                   extra_env={"PS_BENCH_CODEC": "int8",
                              "PS_CODEC_EF": "0"},
                   mode="quantized_push")
    assert r["push_gbps"] > 0
    assert r["pull_p99_ms"] >= r["pull_p50_ms"] >= 0


def _bench_record(**over):
    rec = {
        "chunk_chunked_push_gbps": 10.0,
        "native_goodput_ratio": 2.0,
        "quantized_goodput_ratio_int8": 2.5,
        "small_op_batching_msgs_ratio": 4.2,
        "kv_storm_msgs_per_s": 1000.0,
        "fault_recovery_detect_s": 1.0,
        "some_untracked_wall_s": 5.0,
    }
    rec.update(over)
    return rec


@pytest.mark.slow
def test_small_op_storm_harness():
    """The small_op_batching section's harness: one short subprocess
    leg of ``--mode small_op_storm`` with the combiner on (real tcp
    cluster via the local tracker) must produce the measurement line
    with batches actually formed and the order-sensitive store check
    passing.  Slow-marked like the dlrm harness: the plane's semantics
    are covered by the fast loopback tests in tests/test_batching.py —
    the ratio itself is the bench's job."""
    from pslite_tpu.benchmark import _small_op_run

    r = _small_op_run(1.0, batch=True)
    assert r["ops"] > 0 and r["msgs_per_s"] > 0
    assert r["ops_per_frame"] > 1.0  # multi-op frames really formed
    assert r["store_exact"]
    assert r["p99_ms"] >= r["p50_ms"] >= 0


@pytest.mark.slow
def test_serving_fanin_harness():
    """The serving_fanin section's harness: one short subprocess leg
    of ``--mode serving_fanin`` with the aggregation planes on (real
    1w+2s tcp cluster via the local tracker) must produce the
    measurement line with the fan-in actually formed (response frames
    per request far below the fan-out) and every spot-checked request
    bit-exact.  Slow-marked like the small-op harness: the plane's
    semantics are covered by the fast loopback tests in
    tests/test_multi_get.py — the ratio itself is the bench's job."""
    from pslite_tpu.benchmark import _serving_fanin_run

    r = _serving_fanin_run(1.0, batch=True)
    assert r["reqs"] > 0 and r["reqs_per_s"] > 0
    assert r["servers"] == 2
    # Fan-in really formed: ~1 frame per contacted server, nowhere
    # near one frame per lookup.
    assert r["frames_per_req"] < r["fanout"] / 4
    assert r["store_exact"]
    assert r["p99_ms"] >= r["p50_ms"] >= 0


def test_bench_diff_gates_serving_fanin(tmp_path):
    """The serving_fanin guard: a collapsing requests/s ratio (or
    ballooning frames/request) fails the check; the PS_BENCH_SKIP
    marker reads as absent, never a vanished metric."""
    import sys as _sys

    _sys.path.insert(0, "tools")
    import bench_diff

    old = tmp_path / "BENCH_r07.json"
    new = tmp_path / "BENCH_r08.json"
    base = _bench_record(serving_fanin_req_ratio=4.0,
                         serving_fanin_frames_per_req=1.6)
    old.write_text(json.dumps(base))
    new.write_text(json.dumps(_bench_record(
        serving_fanin_req_ratio=4.0,
        serving_fanin_frames_per_req=8.0,  # 5x more frames: regression
    )))
    assert bench_diff.main([str(old), str(new)]) == 1
    rec = _bench_record()
    rec["serving_fanin_skipped"] = "PS_BENCH_SKIP"
    new.write_text(json.dumps(rec))
    assert bench_diff.main([str(old), str(new)]) == 0


def test_bench_diff_gates_small_op_ratio(tmp_path):
    """The small_op_batching guard: a collapsing msgs ratio (or a
    ballooning low-load p50 ratio) fails the check; the section's
    PS_BENCH_SKIP marker reads as absent, never a vanished metric."""
    import sys as _sys

    _sys.path.insert(0, "tools")
    import bench_diff

    old = tmp_path / "BENCH_r07.json"
    new = tmp_path / "BENCH_r08.json"
    old.write_text(json.dumps(_bench_record()))
    new.write_text(json.dumps(_bench_record(
        small_op_batching_msgs_ratio=2.0,  # -52%: regression
    )))
    assert bench_diff.main([str(old), str(new)]) == 1
    rec = _bench_record()
    del rec["small_op_batching_msgs_ratio"]
    rec["small_op_batching_skipped"] = "PS_BENCH_SKIP"
    new.write_text(json.dumps(rec))
    assert bench_diff.main([str(old), str(new)]) == 0


def test_bench_diff_history(tmp_path):
    """``bench_diff --history`` (ISSUE 10 satellite): the full
    BENCH_r*.json trajectory renders one sparkline row per guarded
    metric with min/max/last, flags a newest-record blind spot, and
    shows per-round status so a blind stretch (the r04/r05 mode) is
    visible at a glance."""
    import sys as _sys

    _sys.path.insert(0, "tools")
    import bench_diff

    for rnd, ratio in ((1, 4.0), (2, 4.4), (3, 4.2)):
        (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(
            json.dumps(_bench_record(small_op_batching_msgs_ratio=ratio)))
    lines = bench_diff.history(str(tmp_path))
    text = "\n".join(lines)
    assert "r01..r03" in text
    row = next(l for l in lines
               if l.strip().startswith("small_op_batching_msgs_ratio"))
    assert "4" in row and "4.4" in row  # min/max/last columns
    assert any(ch in row for ch in bench_diff._SPARK)
    # A blind newest round: the metric row flags it, and the round
    # status line shows zero guarded fields.
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(
        {"error": "no device", "sections_done": []}))
    lines2 = bench_diff.history(str(tmp_path))
    text2 = "\n".join(lines2)
    assert "BLIND" in text2
    # The blind round renders an explicit ∅ sparkline cell (distinct
    # from '·' = metric predates its section) plus the legend.
    row2 = next(l for l in lines2
                if l.strip().startswith("small_op_batching_msgs_ratio"))
    assert "∅" in row2 and "∅ blind" in row2
    assert any("legend" in l for l in lines2)
    # CLI flag: exits 0 and prints the table.
    assert bench_diff.main(["--history", "--dir", str(tmp_path)]) == 0


def test_bench_diff_guard(tmp_path):
    """tools/bench_diff.py (``make bench-check``): per-section deltas,
    exit 0 within threshold, exit nonzero on a >25% regression in a
    guarded transport metric — direction-aware (a LOWER detect time
    passes, a lower goodput ratio fails), untracked fields never
    gate."""
    import sys as _sys

    _sys.path.insert(0, "tools")
    import bench_diff

    old = tmp_path / "BENCH_r07.json"
    new = tmp_path / "BENCH_r08.json"
    old.write_text(json.dumps(_bench_record()))
    # Within threshold + an improvement + untracked field regressing.
    new.write_text(json.dumps(_bench_record(
        chunk_chunked_push_gbps=9.0,     # -10%: ok
        fault_recovery_detect_s=0.5,     # lower = better
        some_untracked_wall_s=50.0,      # untracked: ignored
    )))
    assert bench_diff.main([str(old), str(new)]) == 0
    # Newest-two discovery inside a directory.
    assert bench_diff.main(["--dir", str(tmp_path)]) == 0
    # A guarded ratio collapsing fails the check.
    new.write_text(json.dumps(_bench_record(
        quantized_goodput_ratio_int8=1.0,  # -60%: regression
    )))
    assert bench_diff.main([str(old), str(new)]) == 1
    # Direction awareness: detect time ballooning fails too.
    new.write_text(json.dumps(_bench_record(
        fault_recovery_detect_s=2.0,
    )))
    assert bench_diff.main([str(old), str(new)]) == 1
    # Threshold is configurable.
    assert bench_diff.main(
        [str(old), str(new), "--threshold", "1.5"]
    ) == 0
    # A guarded metric VANISHING from the newer record fails loudly —
    # a crashed section must never read as a pass (the r04/r05 blind-
    # record failure mode).
    rec = _bench_record()
    del rec["quantized_goodput_ratio_int8"]
    new.write_text(json.dumps(rec))
    assert bench_diff.main([str(old), str(new)]) == 1


def test_bench_diff_skipped_sections_not_regressions(tmp_path):
    """A section that degraded with an explicit ``{"skipped": reason}``
    (device down, toolchain absent) must read as ABSENT, not as a
    vanished-metric regression — `make bench-check` on a device-down
    round must still pass."""
    import sys as _sys

    _sys.path.insert(0, "tools")
    import bench_diff

    old = tmp_path / "BENCH_r07.json"
    new = tmp_path / "BENCH_r08.json"
    old.write_text(json.dumps(_bench_record()))
    rec = _bench_record()
    # The native section skipped this round: its guarded metrics are
    # gone but the skip marker names why.
    del rec["native_goodput_ratio"]
    rec["native_skipped"] = "native core unavailable"
    new.write_text(json.dumps(rec))
    assert bench_diff.main([str(old), str(new)]) == 0
    # Without the marker the same vanishing still fails (r04/r05 mode).
    rec2 = _bench_record()
    del rec2["native_goodput_ratio"]
    new.write_text(json.dumps(rec2))
    assert bench_diff.main([str(old), str(new)]) == 1


def test_bench_check_without_records(tmp_path):
    """`make bench-check` wiring (tier-1 smoke): the repo commits no
    BENCH_r*.json (chip numbers live in PERF_LEDGER.jsonl), and
    bench_diff with no records to compare returns 0 — the target stays
    runnable on every checkout."""
    import sys as _sys

    _sys.path.insert(0, "tools")
    import bench_diff

    assert bench_diff.newest_two(str(tmp_path)) is None
    assert bench_diff.main(["--dir", str(tmp_path)]) == 0
    # And the Makefile target that CI runs exists.
    mk = open("/root/repo/Makefile").read()
    assert "bench-check:" in mk and "bench_diff" in mk


def test_multi_tenant_admission_probe():
    """The multi_tenant section's admission half (docs/qos.md): the
    loopback flood sheds with OPT_OVERLOAD fast-fails, nothing hangs,
    store bit-exact at applied-count."""
    from pslite_tpu.benchmark import admission_probe

    r = admission_probe()
    assert r["applied"] + r["shed"] == r["offered"]
    assert r["shed"] > 0
    assert r["store_exact"]


@pytest.mark.slow
def test_dlrm_serve_harness():
    """The multi_tenant section's DLRM half: one subprocess leg of
    ``--mode dlrm_serve`` with the hot cache on (real tcp cluster via
    the local tracker) must produce the measurement line with a
    nonzero hit rate and bit-exact spot checks.  Slow-marked: the
    tier-1 wall budget is tight and the cache semantics are already
    covered by the fast loopback tests in tests/test_qos.py — this
    harness is exercised by the bench itself."""
    from pslite_tpu.benchmark import _dlrm_run

    r = _dlrm_run(150, cache=True)
    assert r["samples"] == 150
    assert r["hit_rate"] > 0.3
    assert r["pull_p50_ms"] >= 0


def test_send_lanes_fanout_harness():
    """The send_lanes section's harness: laned fan-out must beat the
    serialized (PS_SEND_LANES=0) replay on a stub transport with a
    fixed per-message delay."""
    from pslite_tpu.benchmark import fanout_wall_times

    laned, serial = fanout_wall_times(n_peers=6, delay_s=0.02, rounds=2)
    assert laned > 0 and serial > 0
    # Serial must cost ~6x the delay; laned ~1-2x.  Keep the bound loose
    # for CI noise but strictly below the no-overlap regime.
    assert laned < serial, (laned, serial)
    assert laned < 0.6 * serial, (laned, serial)
