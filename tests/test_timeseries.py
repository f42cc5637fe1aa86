"""Continuous telemetry plane (docs/observability.md): ClusterHistory
windowed math, the SLO watchdog, psmon --watch / --serve, and the
fault flight recorder."""

import glob
import json
import os
import re
import sys
import time
import urllib.request

import numpy as np
import pytest

from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker
from pslite_tpu.environment import Environment
from pslite_tpu.telemetry import (
    ClusterHistory,
    FlightRecorder,
    Watchdog,
    bucket_quantile,
    merge_bucket_lists,
    parse_slo,
)
from pslite_tpu.utils.logging import CheckError

from helpers import LoopbackCluster

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import psmon  # noqa: E402


# -- synthetic snapshot helpers ----------------------------------------------


def _snap(node_id=9, role="worker", counters=None, gauges=None,
          hists=None, routing=None):
    s = {
        "node_id": node_id, "role": role,
        "metrics": {
            "counters": counters or {},
            "gauges": gauges or {},
            "histograms": hists or {},
            "topk": {},
            "uptime_s": 10.0,
        },
    }
    if routing is not None:
        s["routing"] = routing
    return s


def _hist(buckets, count, lo=1e-6, mn=1e-4, mx=0.5):
    return {"count": count, "sum": 0.0, "min": mn, "max": mx,
            "lo": lo, "buckets": buckets}


# -- windowed rate / quantile math -------------------------------------------


def test_windowed_rate_from_counter_deltas():
    h = ClusterHistory(env=None, interval_s=1.0)
    h.ingest({9: _snap(counters={"van.sent_messages": 100})}, wall=100.0)
    assert h.rate(9, "van.sent_messages") is None  # one sample: no window
    h.ingest({9: _snap(counters={"van.sent_messages": 350})}, wall=102.0)
    assert h.rate(9, "van.sent_messages") == pytest.approx(125.0)
    # Absent counter reads 0 -> 0 rate; unknown node reads None.
    assert h.rate(9, "no.such.counter") == 0.0
    assert h.rate(77, "van.sent_messages") is None
    # A registry reset (negative delta) poisons the window, not the rate.
    h.ingest({9: _snap(counters={"van.sent_messages": 5})}, wall=104.0)
    assert h.rate(9, "van.sent_messages", window_s=2.5) is None


def test_windowed_quantile_from_bucket_deltas():
    """The windowed p50 reflects ONLY the window's observations: the
    cumulative histogram holds old fast samples, the window all-slow."""
    h = ClusterHistory(env=None, interval_s=1.0)
    fast = [[10, 100]]                 # ~0.5-1 ms mass, pre-window
    slow = [[10, 100], [18, 50]]       # window adds ~0.13-0.26 s mass
    h.ingest({9: _snap(hists={"kv.push_latency_s": _hist(fast, 100)})},
             wall=0.0)
    h.ingest({9: _snap(hists={"kv.push_latency_s": _hist(slow, 150)})},
             wall=2.0)
    q = h.window_quantile(9, "kv.push_latency_s", 0.5)
    assert q is not None and 0.1 < q < 0.3, q
    # The cumulative snapshot's own p50 would still sit in the fast
    # mass — the windowed view is the one that sees the regression.
    cum = bucket_quantile(merge_bucket_lists(slow), 1e-6, 0.5)
    assert cum < 0.01
    # Merged multi-histogram window (the psmon request column).
    q2 = h.window_quantile(
        9, ["kv.push_latency_s", "kv.pull_latency_s"], 0.5)
    assert q2 == pytest.approx(q)
    # No observations inside the window -> None, not a stale estimate.
    h.ingest({9: _snap(hists={"kv.push_latency_s": _hist(slow, 150)})},
             wall=3.0)
    assert h.window_quantile(9, "kv.push_latency_s", 0.5,
                             window_s=0.5) is None


def test_epoch_and_membership_change_log():
    h = ClusterHistory(env=None, interval_s=1.0)
    r0 = {"epoch": 0, "active": [0, 1], "leaving": []}
    r1 = {"epoch": 1, "active": [0, 1, 2], "leaving": []}
    h.ingest({1: _snap(1, "scheduler", routing=r0)}, wall=0.0)
    h.ingest({1: _snap(1, "scheduler", routing=r1),
              8: _snap(8, "server", routing=r1)}, wall=1.0)
    log = h.membership_log()
    assert [e["change"] for e in log] == ["epoch", "epoch",
                                         "node_appeared"]
    assert log[1]["epoch"] == 1 and log[1]["active"] == [0, 1, 2]
    assert log[2]["node_id"] == 8


def test_departed_server_retires_from_history():
    """A server that cleanly LEFT via elastic membership must not read
    as perpetually stale: its series retires when the routing block's
    active+leaving set drops its rank (node_stale is for nodes that
    SHOULD be answering)."""
    from pslite_tpu.base import server_rank_to_id

    wd = Watchdog(None)
    h = ClusterHistory(env=None, interval_s=1.0, watchdog=wd)
    s0, s1 = server_rank_to_id(0), server_rank_to_id(1)
    r0 = {"epoch": 1, "active": [0, 1], "leaving": []}
    r1 = {"epoch": 2, "active": [0], "leaving": []}
    h.ingest({1: _snap(1, "scheduler", routing=r0),
              s0: _snap(s0, "server"), s1: _snap(s1, "server")}, wall=0.0)
    # Rank 1 decommissions; it stops replying from now on.
    h.ingest({1: _snap(1, "scheduler", routing=r1),
              s0: _snap(s0, "server")}, wall=1.0)
    assert s1 not in h.node_ids()
    for w in (2.0, 3.0, 4.0, 5.0, 6.0, 7.0):
        h.ingest({1: _snap(1, "scheduler", routing=r1),
                  s0: _snap(s0, "server")}, wall=w)
    assert h.stale_ages() == {}
    assert not [e for e in wd.events(min_severity="warn")
                if e.rule == "node_stale"], wd.events()
    assert any(c["change"] == "node_departed" and c["node_id"] == s1
               for c in h.membership_log())


def test_stale_ages_and_trend():
    h = ClusterHistory(env=None, interval_s=1.0)
    for w in (0.0, 1.0, 2.0):
        round_ = {9: _snap(9, counters={"van.sent_messages": int(10 * w)})}
        if w < 2.0:
            round_[8] = _snap(8, "server")
        h.ingest(round_, wall=w)
    ages = h.stale_ages()
    assert set(ages) == {8} and ages[8] == pytest.approx(1.0)
    tr = h.trend(9, "van.sent_messages")
    assert tr == [pytest.approx(10.0), pytest.approx(10.0)]


# -- SLO watchdog ------------------------------------------------------------


def test_slo_spec_parsing():
    rules = parse_slo("shed_rate=0.5:5,queue_growth=off")
    assert rules["shed_rate"].warn == 0.5
    assert rules["shed_rate"].crit == 5
    assert not rules["queue_growth"].enabled
    assert rules["req_p99"].warn == 0.5  # untouched default
    with pytest.raises(CheckError):
        parse_slo("no_such_rule=1:2")
    with pytest.raises(CheckError):
        parse_slo("shed_rate=5:1")  # warn > crit
    # Environment wiring.
    wd = Watchdog(Environment({"PS_SLO": "repl_lag=10:20"}))
    assert wd.rules["repl_lag"].warn == 10


def test_watchdog_trips_on_shed_rate_and_stays_quiet_idle():
    wd = Watchdog(None)
    h = ClusterHistory(env=None, interval_s=1.0, watchdog=wd)
    h.ingest({8: _snap(8, "server",
                       counters={"tenant.bulk.shed": 0,
                                 "qos.shed_requests": 0})}, wall=0.0)
    assert wd.events(min_severity="warn") == []
    h.ingest({8: _snap(8, "server",
                       counters={"tenant.bulk.shed": 100,
                                 "qos.shed_requests": 100})}, wall=2.0)
    evs = wd.events(min_severity="warn")
    crit = [e for e in evs if e.rule == "shed_rate"
            and e.severity == "crit"]
    assert crit, evs
    assert any(e.tenant == "bulk" for e in crit)
    ev = crit[0]
    assert ev.node_id == 8 and ev.value == pytest.approx(50.0)
    assert ev.threshold == 10.0 and ev.window_s > 0
    json.dumps(ev.as_dict())  # structured + serializable
    # Idle control: several identical samples -> zero WARN/CRIT.
    wd2 = Watchdog(None)
    h2 = ClusterHistory(env=None, interval_s=1.0, watchdog=wd2)
    for w in range(4):
        h2.ingest({8: _snap(8, "server",
                            counters={"tenant.bulk.shed": 100,
                                      "van.sent_messages": 500},
                            gauges={"van.lane_depth": 0.0,
                                    "replication.lag": 0.0})},
                  wall=float(w))
    assert wd2.events(min_severity="warn") == []


def test_watchdog_replication_lag_and_queue_growth():
    wd = Watchdog(None)
    h = ClusterHistory(env=None, interval_s=1.0, watchdog=wd)
    h.ingest({8: _snap(8, "server",
                       gauges={"replication.lag": 0.0,
                               "van.lane_depth": 0.0})}, wall=0.0)
    # Replica chain died: forwards park in the lanes, lag climbs.
    h.ingest({8: _snap(8, "server",
                       gauges={"replication.lag": 100.0,
                               "van.lane_depth": 0.0})}, wall=1.0)
    evs = wd.events(min_severity="warn")
    lag = [e for e in evs if e.rule == "repl_lag"]
    assert lag and lag[0].severity == "warn"  # 100 in [64, 512)
    # Queue growth across the window trips its own rule.
    h.ingest({8: _snap(8, "server",
                       gauges={"replication.lag": 100.0,
                               "van.lane_depth": 5000.0})}, wall=2.0)
    growth = [e for e in wd.events(min_severity="warn")
              if e.rule == "queue_growth"]
    assert growth and growth[0].severity == "crit"


def test_watchdog_retransmit_burst_and_node_stale():
    wd = Watchdog(None)
    h = ClusterHistory(env=None, interval_s=1.0, watchdog=wd)
    h.ingest({9: _snap(counters={"resender.retransmits": 0}),
              8: _snap(8, "server")}, wall=0.0)
    h.ingest({9: _snap(counters={"resender.retransmits": 200})}, wall=2.0)
    rules = {e.rule for e in wd.events(min_severity="warn")}
    assert "retransmit_burst" in rules
    # Node 8 answered nothing for 2 intervals -> node_stale WARN.
    h.ingest({9: _snap(counters={"resender.retransmits": 200})}, wall=3.0)
    stale = [e for e in wd.events(min_severity="warn")
             if e.rule == "node_stale"]
    assert stale and stale[0].node_id == 8


def test_watchdog_holdoff_and_escalation():
    """A sustained breach emits once per window; an escalation to CRIT
    always emits."""
    wd = Watchdog(None)
    h = ClusterHistory(env=None, interval_s=10.0, watchdog=wd)
    h.ingest({8: _snap(8, gauges={"replication.lag": 0.0})}, wall=0.0)
    h.ingest({8: _snap(8, gauges={"replication.lag": 100.0})}, wall=1.0)
    h.ingest({8: _snap(8, gauges={"replication.lag": 100.0})}, wall=2.0)
    assert len([e for e in wd.events() if e.rule == "repl_lag"]) == 1
    h.ingest({8: _snap(8, gauges={"replication.lag": 1000.0})}, wall=3.0)
    lag = [e for e in wd.events() if e.rule == "repl_lag"]
    assert [e.severity for e in lag] == ["warn", "crit"]


# -- psmon merged quantiles + stale rows (satellites) ------------------------


def test_psmon_merged_push_pull_quantiles():
    """The request column merges the RAW buckets of both histograms:
    a slow-but-quiet pull path must move the merged p99 (the old
    busier-path-wins approximation reported the fast push numbers)."""
    m = {
        "histograms": {
            # 90 fast pushes (~bucket 10 = 0.5-1ms)
            "kv.push_latency_s": _hist([[10, 90]], 90, mn=5e-4, mx=1e-3),
            # 10 slow pulls (~bucket 18 = 0.13-0.26s)
            "kv.pull_latency_s": _hist([[18, 10]], 10, mn=0.13, mx=0.26),
        },
    }
    p50, p99 = psmon._req_quantiles(m)
    assert p50 < 2.0       # ms — the bulk is fast
    assert p99 > 100.0     # ms — the slow tail is VISIBLE
    # The old approximation (busier path wins) would have said ~1ms.
    busy_p99 = 1e-3 * 1e3
    assert p99 > 50 * busy_p99


def test_psmon_stale_rows_and_trace_drop_warning():
    snap = {9: _snap(9, counters={"trace.dropped_events": 7})}
    table = psmon.format_table(snap, stale={11: 12.5})
    assert "last seen 12.5s ago" in table
    assert "11" in table
    assert "dropped 7 span(s)" in table
    # Clean snapshot: no warning block, no stale rows.
    clean = psmon.format_table({9: _snap(9)})
    assert "dropped" not in clean and "last seen" not in clean


def test_tracer_dropped_spans_land_on_registry():
    from pslite_tpu.telemetry.metrics import Registry
    from pslite_tpu.telemetry.tracing import Tracer

    reg = Registry()
    tr = Tracer(Environment({"PS_TRACE_SAMPLE": "1"}), "worker",
                metrics=reg)
    tr.MAX_EVENTS = 2  # instance shadow for the test
    for _ in range(5):
        tr.span(123, "request", 0.0, 1.0)
    assert tr.dropped == 3
    assert reg.snapshot()["counters"]["trace.dropped_events"] == 3


# -- OpenMetrics / Prometheus exposition -------------------------------------


_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^}]*\})? (?P<value>\S+)$"
)


def _parse_prometheus(text):
    """Minimal exposition parser: returns (types, samples) where
    samples is [(name, labels_dict, value_str)].  Raises on any line
    that is neither a comment nor a well-formed sample."""
    types = {}
    samples = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split()
            if parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        labels = {}
        if m.group("labels"):
            for kv in m.group("labels")[1:-1].split(","):
                k, _, v = kv.partition("=")
                labels[k] = v.strip('"')
        float(m.group("value").replace("+Inf", "inf"))  # numeric
        samples.append((m.group("name"), labels, m.group("value")))
    return types, samples


def _snap_with_hist():
    return {
        9: _snap(9, counters={"van.sent_messages": 10,
                              "tenant.bulk.shed": 3},
                 gauges={"van.lane_depth": 2.0},
                 hists={"kv.push_latency_s": _hist(
                     [[10, 5], [12, 4], [18, 6]], 15)}),
        8: _snap(8, "server", counters={"kv.server_push_requests": 4}),
    }


def test_prometheus_exposition_parses_and_le_monotone():
    text = psmon.to_prometheus(_snap_with_hist())
    types, samples = _parse_prometheus(text)
    assert types["pslite_van_sent_messages_total"] == "counter"
    assert types["pslite_van_lane_depth"] == "gauge"
    assert types["pslite_kv_push_latency_s"] == "histogram"
    # Tenant counters collapse into one family with a tenant label.
    tenant = [(labels, v) for name, labels, v in samples
              if name == "pslite_tenant_shed_total"]
    assert tenant == [({"node": "9", "role": "worker",
                        "tenant": "bulk"}, "3")]
    # Histogram contract: le strictly increasing, cumulative counts
    # non-decreasing, +Inf last and equal to _count.
    buckets = [(labels["le"], int(v)) for name, labels, v in samples
               if name == "pslite_kv_push_latency_s_bucket"]
    assert buckets[-1][0] == "+Inf"
    les = [float(le.replace("+Inf", "inf")) for le, _ in buckets]
    counts = [c for _, c in buckets]
    assert les == sorted(les) and len(set(les)) == len(les)
    assert counts == sorted(counts)
    count = next(int(v) for name, _l, v in samples
                 if name == "pslite_kv_push_latency_s_count")
    assert buckets[-1][1] == count == 15
    # Every node appears with its labels.
    assert any(l.get("node") == "8" and l.get("role") == "server"
               for _n, l, _v in samples)


def test_prometheus_serve_endpoint():
    snap = _snap_with_hist()
    httpd = psmon.serve(lambda: snap, 0)
    try:
        port = httpd.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as resp:
            assert resp.status == 200
            ctype = resp.headers["Content-Type"]
            body = resp.read().decode()
        assert ctype == psmon.PROM_CONTENT_TYPE
        assert "version=0.0.4" in ctype
        types, _samples = _parse_prometheus(body)
        assert types["pslite_van_sent_messages_total"] == "counter"
        # Unknown paths 404 instead of crashing the server.
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        httpd.shutdown()


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path):
    env = Environment({"PS_TRACE_DIR": str(tmp_path),
                       "PS_FLIGHT_EVENTS": "16"})
    fr = FlightRecorder(env, "server")
    fr.node_id = 8
    assert fr.dump() is None  # nothing recorded, nothing written
    for i in range(20):
        fr.record("overload_shed", tenant="bulk", n=i)
    assert fr.num_events == 16 and fr.dropped == 4
    assert not fr.abnormal
    assert fr.dump_if_abnormal() is None  # warn events alone: clean stop
    fr.record("check_failure", severity="crit", error="boom")
    path = fr.dump_if_abnormal()
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["abnormal"] and doc["node_id"] == 8
    assert doc["abnormal_reason"].startswith("check_failure")
    kinds = [e["kind"] for e in doc["events"]]
    assert kinds[-1] == "check_failure"
    assert all("ts_us" in e for e in doc["events"])
    # Timestamps ride the shared wall-anchored monotonic timebase.
    assert doc["events"][0]["ts_us"] <= doc["events"][-1]["ts_us"]


def test_flight_dump_on_induced_van_abort(tmp_path):
    """A chaos crash-at-phase abort marks the victim's stop abnormal
    and Van.stop() writes the flight dump with the chaos_crash event —
    the postmortem attachment chaos-test failures rely on."""
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1, van_type="chaos+loopback",
        env_extra={"PS_TRACE_DIR": str(tmp_path)},
        per_node_env={"server0": {"PS_CHAOS": "seed=3,crash=recv:3"}},
    )
    cluster.start()
    servers, workers = [], []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        workers.append(w)
        keys = np.array([3], dtype=np.uint64)
        vals = np.ones(16, np.float32)
        for _ in range(3):
            w.wait(w.push(keys, vals))
        # Past the crash budget: fire-and-forget pushes (the server is
        # about to go deaf; waiting would hang).
        for _ in range(8):
            w.push(keys, vals)
        victim = cluster.servers[0].van
        t0 = time.monotonic()
        while not victim.chaos_crashed.is_set():
            assert time.monotonic() - t0 < 10, "chaos crash never tripped"
            w.push(keys, vals)
            time.sleep(0.02)
    finally:
        for po in cluster.all_nodes():
            try:
                po.van.stop()
            except Exception:
                pass
    files = glob.glob(str(tmp_path / "pslite_flight_server_*.json"))
    assert files, "abnormal stop produced no flight dump"
    doc = json.load(open(files[0]))
    assert doc["abnormal"]
    assert any(e["kind"] == "chaos_crash" and e["severity"] == "crit"
               for e in doc["events"])


# -- live cluster: sampler, watch path, overload storm -----------------------


def test_watch_path_end_to_end_smoke():
    """--watch acceptance: sampler on (PS_METRICS_INTERVAL), history
    populated with every node, windowed rates nonzero, health clean,
    format_watch renders."""
    cluster = LoopbackCluster(
        num_workers=2, num_servers=2,
        env_extra={"PS_METRICS_INTERVAL": "0.2"},
    )
    cluster.start()
    servers, workers = [], []
    try:
        for po in cluster.servers:
            s = KVServer(0, postoffice=po)
            s.set_request_handle(KVServerDefaultHandle())
            servers.append(s)
        workers = [KVWorker(0, 0, postoffice=po)
                   for po in cluster.workers]
        hist = cluster.scheduler.history
        assert hist is not None and hist.running, \
            "PS_METRICS_INTERVAL did not start the sampler"
        keys = np.array([3, 2 ** 63 + 9], dtype=np.uint64)
        vals = np.ones(2 * 16, np.float32)
        deadline = time.monotonic() + 15
        while hist.samples < 4:
            assert time.monotonic() < deadline, "sampler never sampled"
            for w in workers:
                w.wait(w.push(keys, vals))
            time.sleep(0.05)
        assert len(hist.node_ids()) == 5  # scheduler + 2s + 2w
        wid = cluster.workers[0].van.my_node.id
        assert hist.rate(wid, "van.sent_messages") > 0
        assert hist.stale_ages() == {}
        # Healthy cluster: ZERO watchdog findings at WARN or above.
        assert cluster.scheduler.health(min_severity="warn") == []
        frame = psmon.format_watch(hist)
        assert "out/s" in frame and "health" in frame
        assert f"\n{wid:>5} " in "\n" + frame
        for w in workers:
            w.stop()
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_overload_storm_trips_shed_crit_and_flight_records(tmp_path):
    """ISSUE 12 acceptance: a tenant overload storm trips the
    shed-rate rule to CRIT within 2 sample intervals, and the victim
    server's flight recorder holds the matching overload_shed
    events."""
    interval = 0.2
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1,
        env_extra={
            "PS_METRICS_INTERVAL": str(interval),
            "PS_TENANTS": "serve:8,train:1",
            "PS_TENANT_QUEUE_LIMIT": "4",
            "PS_SLO": "shed_rate=0.5:2,req_p99=off,queue_growth=off",
            "PS_TRACE_DIR": str(tmp_path),
        },
    )
    cluster.start()
    servers, workers = [], []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        workers.append(w)
        from pslite_tpu.kv.kv_app import OverloadError

        keys = np.arange(8, dtype=np.uint64)
        vals = np.ones(8 * 1024, np.float32)
        shed = 0
        storm_end = time.monotonic() + 6 * interval
        while time.monotonic() < storm_end:
            tss = [w.push(keys, vals, tenant="train") for _ in range(32)]
            for ts in tss:
                try:
                    w.wait(ts)
                except OverloadError:
                    shed += 1
        assert shed > 0, "flood never tripped the tenant bound"
        # Within 2 further sample intervals the watchdog reports CRIT.
        deadline = time.monotonic() + 2 * interval + 2.0
        crit = []
        while time.monotonic() < deadline:
            crit = [e for e in cluster.scheduler.health("crit")
                    if e.rule == "shed_rate"]
            if crit:
                break
            time.sleep(interval / 2)
        assert crit, cluster.scheduler.health(min_severity="info")
        assert any(e.tenant == "train" for e in crit)
        # The flight recorder kept the matching per-shed events.
        sheds = cluster.servers[0].flight.events("overload_shed")
        assert sheds and any(e.get("tenant") == "train" for e in sheds)
        # On-demand dump contains them too (the chaos-postmortem path).
        path = cluster.servers[0].flight.dump(
            str(tmp_path / "flight_server.json"))
        doc = json.load(open(path))
        assert any(e["kind"] == "overload_shed" for e in doc["events"])
        w.stop()
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_postoffice_health_empty_without_history():
    cluster = LoopbackCluster(num_workers=1, num_servers=1)
    cluster.start()
    try:
        assert cluster.scheduler.health() == []
        assert cluster.workers[0].health() == []
    finally:
        cluster.finalize()


# -- bench windowed rates (satellite) ----------------------------------------


def test_kv_storm_reports_windowed_rates():
    from pslite_tpu.benchmark import kv_loopback_storm

    r = kv_loopback_storm(n_workers=1, n_servers=1, msgs_per_worker=5)
    worker = next(v for k, v in r["telemetry"].items()
                  if k.startswith("worker"))
    rates = worker["windowed_per_s"]
    # 5 pushes over the measured wall: the windowed rate must agree
    # with msgs/wall, NOT with count/uptime (uptime >> wall here).
    assert rates["kv.pushes"] == pytest.approx(
        5.0 / r["wall_s"], rel=0.05)
    server = next(v for k, v in r["telemetry"].items()
                  if k.startswith("server"))
    assert server["windowed_per_s"]["kv.server_push_requests"] > 0
