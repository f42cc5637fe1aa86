#!/usr/bin/env python
"""bench_diff — compare the two newest ``BENCH_r*.json`` records.

Prints per-section deltas for the always-on transport sections (the
ones ``bench.py`` runs regardless of device availability) and exits
nonzero when any DIRECTIONAL metric regressed by more than the
threshold (default 25%) — the trajectory guard ``make bench-check``
runs, referenced from ``tests/test_bench_smoke.py``.

Only metrics listed in ``TRANSPORT_METRICS`` gate the exit status:
each entry knows which direction is good, so a higher p99 fails while
a higher goodput passes.  Everything else numeric is printed as
context but never fails the check (absolute walls move with host
load; the curated list holds the ratios and rates that are
host-comparable).

Usage::

    python tools/bench_diff.py                 # newest two BENCH_r*.json
    python tools/bench_diff.py OLD.json NEW.json
    python tools/bench_diff.py --threshold 0.4
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

# metric -> "higher" (bigger is better) or "lower".  Grouped by the
# bench section that emits them; every section here is always-on
# (bench.py runs it with or without a device backend).
TRANSPORT_METRICS: Dict[str, str] = {
    # send_lanes
    "send_lanes_overlap_x": "higher",
    # server_apply
    "server_apply_sharded_msgs_per_s": "higher",
    "server_apply_speedup_x": "higher",
    # chunk_streaming
    "chunk_chunked_push_gbps": "higher",
    "chunk_hol_p99_ratio": "higher",
    # native_goodput
    "native_native_push_gbps": "higher",
    "native_goodput_ratio": "higher",
    # quantized_push (docs/compression.md) — BOTH halves of the
    # acceptance: effective goodput up, priority-pull tail bounded.
    "quantized_int8_push_gbps": "higher",
    "quantized_fp8_e4m3_push_gbps": "higher",
    "quantized_goodput_ratio_int8": "higher",
    "quantized_goodput_ratio_fp8_e4m3": "higher",
    "quantized_p99_ratio_int8": "lower",
    "quantized_p99_ratio_fp8_e4m3": "lower",
    # multi_tenant (docs/qos.md) — isolation, cache, and hit rate.
    "multi_tenant_p99_ratio": "lower",
    "multi_tenant_dlrm_p50_ratio": "higher",
    "multi_tenant_hit_rate": "higher",
    # small_op_batching (docs/batching.md) — the ops/s multiple of the
    # aggregation plane, and the low-load latency it must not cost.
    "small_op_batching_msgs_ratio": "higher",
    "small_op_batching_batched_msgs_per_s": "higher",
    "small_op_batching_low_load_p50_ratio": "lower",
    # serving_fanin (docs/batching.md) — multi-get + response
    # aggregation: the requests/s multiple of the fan-in plane, the
    # ~1-RTT response-frames-per-request it must hold, and the
    # low-load single-pull latency it must not cost.
    "serving_fanin_req_ratio": "higher",
    "serving_fanin_agg_reqs_per_s": "higher",
    "serving_fanin_frames_per_req": "lower",
    "serving_fanin_low_load_p50_ratio": "lower",
    # replica_read (docs/serving_reads.md) — the reads/s multiple of
    # spreading pulls over the whole replica chain (k=3 vs k=1), and
    # the read-your-writes guarantee it must NEVER trade away.
    "replica_read_tput_ratio": "higher",
    "replica_read_k3_reqs_per_s": "higher",
    "replica_read_ryw_violations": "lower",
    "replica_read_ns_flip_errors": "lower",
    # elastic_scale (docs/elasticity.md) — the serving tail must stay
    # bounded through a live 2->4->2 migration window, and the scale
    # round trip itself must not regress.
    "elastic_p99_ratio": "lower",
    "elastic_scale_2_4_2_wall_s": "lower",
    # autopilot (docs/autopilot.md) — the self-driving loop must keep
    # per-server load near the mean (a ratio drifting back toward ~2
    # means the skew remediation stopped working) with ZERO manual
    # operator actions (any nonzero value is a regression by
    # definition: the loop needed a human).
    "autopilot_load_skew_ratio": "lower",
    "autopilot_operator_actions": "lower",
    # durable_store (docs/durability.md) — the beyond-RAM serving tax
    # (Zipf hot-set p99, tiered vs all-RAM; acceptance <= 2x) and the
    # full-cluster-kill restore wall.
    "durable_hot_p99_ratio": "lower",
    "durable_restore_s": "lower",
    # kv_telemetry
    "kv_storm_msgs_per_s": "higher",
    # wire (docs/observability.md) — wire-plane efficiency of the
    # bursty small-op tcp storm: kernel crossings and frames per
    # logical op must not creep up (batching regressing to singletons
    # or the vectored writer degenerating shows up here first).
    "wire_syscalls_per_op": "lower",
    "wire_frames_per_op": "lower",
    # fault_recovery
    "fault_recovery_detect_s": "lower",
    "fault_recovery_failover_pull_s": "lower",
}

# Section key prefixes, used to map a guarded metric back to the
# section that emits it.  A section that degraded on purpose emits
# ``{"skipped": <reason>}`` — its fields then land as
# ``<prefix>skipped`` in the record — and its guarded metrics are
# treated as ABSENT (a device-down round must not read as a vanished-
# metric regression) rather than failed.
SECTION_PREFIXES = (
    "send_lanes_", "server_apply_", "chunk_", "native_", "quantized_",
    "multi_tenant_", "small_op_batching_", "serving_fanin_",
    "replica_read_", "elastic_", "autopilot_", "durable_",
    "kv_tracing_", "kv_", "fault_recovery_", "van_", "wire_",
)

# Hard invariants: metrics that must be exactly ZERO in every record.
# The ratio guard above cannot express them (a 0 -> 0 pair is skipped,
# and 0 -> N has no finite delta); any nonzero value here is a
# regression outright — e.g. the autopilot acceptance requires the
# storm to complete with no manual operator actions at all.
MUST_BE_ZERO = ("autopilot_operator_actions",)


def _section_skipped(rec: dict, key: str) -> bool:
    """True when the section emitting guarded metric ``key`` recorded
    an explicit skip in ``rec`` instead of running."""
    for p in SECTION_PREFIXES:
        if key.startswith(p) and f"{p}skipped" in rec:
            return True
    return False


def _round_of(path: str) -> int:
    m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else -1


def newest_two(directory: str) -> Optional[Tuple[str, str]]:
    """(older, newer) of the two highest-numbered BENCH_r*.json."""
    recs = sorted(
        (p for p in glob.glob(os.path.join(directory, "BENCH_r*.json"))
         if _round_of(p) >= 0),
        key=_round_of,
    )
    if len(recs) < 2:
        return None
    return recs[-2], recs[-1]


# Top-level fields that are context-only by construction and never
# comparable across rounds: the kv_telemetry section's windowed-rate
# roll-ups depend on the measured interval and host load, and the
# kv_tracing section's tail-trace counts/stage shares are shaped by
# host load and the uniform keep floor — diffing either only produces
# noise lines (docs/observability.md).
IGNORED_PREFIXES = ("kv_windowed_", "kv_tracing_")


def _numeric_items(rec: dict) -> Dict[str, float]:
    out = {}
    for k, v in rec.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        if any(k.startswith(p) for p in IGNORED_PREFIXES):
            continue
        out[k] = float(v)
    return out


def compare(old: dict, new: dict,
            threshold: float = 0.25) -> Tuple[List[str], List[str]]:
    """(report lines, regression lines)."""
    o, n = _numeric_items(old), _numeric_items(new)
    lines: List[str] = []
    regressions: List[str] = []
    for key in sorted(set(o) & set(n)):
        ov, nv = o[key], n[key]
        if ov == 0:
            continue
        delta = (nv - ov) / abs(ov)
        direction = TRANSPORT_METRICS.get(key)
        tag = ""
        if direction is not None:
            adverse = -delta if direction == "higher" else delta
            if adverse > threshold:
                tag = "  << REGRESSION"
                regressions.append(
                    f"{key}: {ov:g} -> {nv:g} "
                    f"({delta:+.1%}, {direction} is better)"
                )
            else:
                tag = "  [guarded]"
        lines.append(f"  {key:<44} {ov:>12g} -> {nv:>12g} "
                     f"({delta:+7.1%}){tag}")
    # A guarded metric that VANISHED from the newer record is the
    # worst regression of all — a crashed/blind section (the r04/r05
    # failure mode this tool exists to catch) must not read as a pass.
    # Exception: a section that recorded an EXPLICIT skip reason
    # (``{"skipped": ...}`` — device down, toolchain absent) is noted
    # but never fails the check; skipping loudly is the designed
    # degrade, not a regression.
    for key in sorted(set(TRANSPORT_METRICS) & set(o) - set(n)):
        if _section_skipped(new, key):
            lines.append(f"  {key:<44} {o[key]:>12g} ->      skipped"
                         f"  [section skipped]")
            continue
        regressions.append(
            f"{key}: {o[key]:g} -> MISSING (section absent or failed "
            f"in the newer record)"
        )
        lines.append(f"  {key:<44} {o[key]:>12g} ->      MISSING"
                     f"  << REGRESSION")
    # Zero-invariant metrics: the ov == 0 guard above skips them, so
    # check the newer record directly — any nonzero value fails.
    for key in MUST_BE_ZERO:
        nv = n.get(key)
        if nv:
            regressions.append(f"{key}: must be 0, got {nv:g}")
            lines.append(f"  {key:<44} {'0':>12} -> {nv:>12g}"
                         f"  << REGRESSION (must be 0)")
    # Sections that disappeared or newly failed are worth a loud note.
    for field in ("sections_failed",):
        if new.get(field):
            lines.append(f"  note: {field} = {new[field]}")
    return lines, regressions


_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(series: List[Optional[float]],
               blind: Optional[List[bool]] = None) -> str:
    """Unicode mini-chart of one metric's round-by-round values.
    Rounds where the metric was absent render as '·' — EXCEPT blind
    device rounds (the record carries an ``error``, e.g. "backend
    init timed out": nothing device-side ran at all), which render as
    an explicit '∅' so a run without a device reads as one, not as a
    metric that merely hadn't been invented yet."""
    blind = blind or [False] * len(series)

    def absent(i: int) -> str:
        return "∅" if blind[i] else "·"

    vals = [v for v in series if v is not None]
    if not vals:
        return "".join(absent(i) for i in range(len(series)))
    lo, hi = min(vals), max(vals)
    span = hi - lo
    out = []
    for i, v in enumerate(series):
        if v is None:
            out.append(absent(i))
        elif span <= 0:
            out.append(_SPARK[3])
        else:
            out.append(_SPARK[min(7, int((v - lo) / span * 7.999))])
    return "".join(out)


def history(directory: str) -> List[str]:
    """Render the FULL ``BENCH_r*.json`` trajectory of every guarded
    transport metric as a min/max/last sparkline table — the
    at-a-glance view that makes a blind stretch (rounds of silently
    missing device numbers) visible immediately instead of only when
    the newest two records happen to straddle it."""
    recs = sorted(
        (p for p in glob.glob(os.path.join(directory, "BENCH_r*.json"))
         if _round_of(p) >= 0),
        key=_round_of,
    )
    if not recs:
        return [f"bench_diff --history: no BENCH_r*.json in {directory}"]
    rounds = [_round_of(p) for p in recs]
    objs = []
    for p in recs:
        try:
            rec = json.load(open(p))
        except Exception:  # noqa: BLE001 - a corrupt record renders absent
            rec = {}
        # The driver wraps bench.py's emitted JSON under "parsed"
        # (alongside the raw cmd/rc/tail provenance) — unwrap so the
        # committed records render their metric fields.
        if isinstance(rec.get("parsed"), dict) and not any(
                k in rec for k in TRANSPORT_METRICS):
            rec = rec["parsed"]
        objs.append(rec)
    lines = [
        f"bench_diff history: rounds r{rounds[0]:02d}..r{rounds[-1]:02d} "
        f"({len(recs)} records, {len(TRANSPORT_METRICS)} guarded metrics)",
    ]
    # Per-round status first: a blind round (error field, zero sections,
    # or no transport fields at all) must be visible even when no
    # guarded metric ever rendered a sparkline cell for it.
    for rnd, rec in zip(rounds, objs):
        sha = str(rec.get("git_sha", ""))[:9] or "-"
        n_metrics = sum(1 for k in TRANSPORT_METRICS if k in rec)
        done = rec.get("sections_done")
        failed = rec.get("sections_failed")
        status = []
        if rec.get("error"):
            status.append(f"ERROR: {str(rec['error'])[:60]}")
        if done is not None:
            status.append(f"{len(done)} sections done"
                          + (f", {len(failed)} failed" if failed else ""))
        if n_metrics == 0:
            status.append("BLIND (no guarded transport fields)")
        lines.append(f"  r{rnd:02d}  sha={sha:<9} "
                     f"guarded={n_metrics:>2}  " + "; ".join(status))
    # Blind device rounds: the record carries an explicit error
    # ("backend init timed out...") — every guarded cell of that round
    # renders '∅', distinct from '·' (metric predates its section).
    blind_rounds = [bool(rec.get("error")) for rec in objs]
    if any(blind_rounds):
        lines.append("")
        lines.append("  legend: ∅ = blind device round (bench errored; "
                     "no device numbers exist), · = metric absent")
    lines.append("")
    lines.append(
        f"  {'metric':<44} {'trend':<{max(5, len(recs))}} "
        f"{'min':>10} {'max':>10} {'last':>10}  dir"
    )
    for key in sorted(TRANSPORT_METRICS):
        series: List[Optional[float]] = []
        for rec in objs:
            v = rec.get(key)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                v = None
            series.append(None if v is None else float(v))
        vals = [v for v in series if v is not None]
        if not vals:
            continue  # metric never emitted (older than its section)
        spark = _sparkline(series, blind_rounds)
        tail = ""
        if series[-1] is None:
            tail = ("   << ∅ blind (newest round errored)"
                    if blind_rounds[-1]
                    else "   << BLIND (absent in newest record)")
        lines.append(
            f"  {key:<44} {spark:<{max(5, len(recs))}} "
            f"{min(vals):>10g} {max(vals):>10g} "
            f"{(series[-1] if series[-1] is not None else float('nan')):>10g}"
            f"  {TRANSPORT_METRICS[key]}" + tail
        )
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("files", nargs="*",
                    help="explicit OLD NEW records (default: the two "
                         "newest BENCH_r*.json in --dir)")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="adverse fractional change that fails the "
                         "check (default 0.25)")
    ap.add_argument("--history", action="store_true",
                    help="render every BENCH_r*.json round per guarded "
                         "metric (min/max/last sparkline table) instead "
                         "of diffing the newest two")
    args = ap.parse_args(argv)
    if args.history:
        print("\n".join(history(args.dir)))
        return 0
    if args.files:
        if len(args.files) != 2:
            ap.error("pass exactly two files (OLD NEW) or none")
        old_path, new_path = args.files
    else:
        pair = newest_two(args.dir)
        if pair is None:
            print("bench_diff: fewer than two BENCH_r*.json records in "
                  f"{args.dir}; nothing to compare")
            return 0
        old_path, new_path = pair
    old = json.load(open(old_path))
    new = json.load(open(new_path))
    print(f"bench_diff: {os.path.basename(old_path)} -> "
          f"{os.path.basename(new_path)} "
          f"(threshold {args.threshold:.0%} on "
          f"{len(TRANSPORT_METRICS)} guarded transport metrics)")
    lines, regressions = compare(old, new, args.threshold)
    print("\n".join(lines) if lines else "  (no shared numeric fields)")
    if regressions:
        print(f"\nbench_diff: {len(regressions)} transport "
              f"regression(s) > {args.threshold:.0%}:")
        for r in regressions:
            print(f"  {r}")
        return 1
    print("\nbench_diff: no guarded transport metric regressed beyond "
          f"{args.threshold:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
