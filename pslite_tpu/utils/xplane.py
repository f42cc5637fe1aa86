"""Minimal XPlane (.xplane.pb) reader: on-device busy time extraction.

``jax.profiler.trace`` (wrapped by :class:`profiling.device_trace`) dumps
an XSpace protobuf per host.  A device-time denominator is the
DEVICE-side timeline: the union of XLA op intervals on the TPU planes
(wall clock also counts the host's dispatch and staging).  This module
parses exactly the fields
needed (wire-format protobuf, no protobuf/tensorflow dependency):

    XSpace { repeated XPlane planes = 1; }
    XPlane { int64 id=1; string name=2; repeated XLine lines=3; }
    XLine  { int64 id=1; string name=2; int64 timestamp_ns=3;
             repeated XEvent events=4; }
    XEvent { int64 metadata_id=1; int64 offset_ps=2; int64 duration_ps=3; }

(Field numbers from tsl/profiler/protobuf/xplane.proto; unknown fields
are skipped by wire type, so schema growth is tolerated.)

Busy time is computed as the union of [offset, offset+duration] intervals
per line, then the union across a plane's lines is NOT taken — parallel
lines (different cores / queues) are summed, matching "device-seconds of
work" rather than span.  For single-core single-queue runs the two
definitions coincide.
"""

from __future__ import annotations

import os
from typing import Dict, Iterator, List, Tuple


def _varint(buf: memoryview, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, int, object]]:
    """(field_number, wire_type, value) over one message's bytes."""
    pos = 0
    n = len(buf)
    while pos < n:
        key, pos = _varint(buf, pos)
        fnum, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 1:
            val = buf[pos : pos + 8]
            pos += 8
        elif wt == 2:
            ln, pos = _varint(buf, pos)
            val = buf[pos : pos + ln]
            pos += ln
        elif wt == 5:
            val = buf[pos : pos + 4]
            pos += 4
        else:  # groups (3/4): not produced by xplane
            raise ValueError(f"unsupported wire type {wt}")
        yield fnum, wt, val


def _line_busy_ps(line_buf: memoryview) -> Tuple[str, int]:
    """(line_name, busy_ps) — busy = union of event intervals."""
    name = ""
    intervals: List[Tuple[int, int]] = []
    for fnum, wt, val in _fields(line_buf):
        if fnum == 2 and wt == 2:
            name = bytes(val).decode("utf-8", "replace")
        elif fnum == 4 and wt == 2:
            off = dur = 0
            for efn, ewt, ev in _fields(val):
                if efn == 2 and ewt == 0:
                    off = ev
                elif efn == 3 and ewt == 0:
                    dur = ev
            if dur > 0:
                intervals.append((off, off + dur))
    if not intervals:
        return name, 0
    intervals.sort()
    busy = 0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy += cur_e - cur_s
    return name, busy


def plane_busy_ps(path: str) -> Dict[str, Dict[str, int]]:
    """{plane_name: {line_name: busy_ps}} for one .xplane.pb file."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, Dict[str, int]] = {}
    for fnum, wt, plane in _fields(space):
        if fnum != 1 or wt != 2:
            continue
        pname = ""
        lines: Dict[str, int] = {}
        for pfn, pwt, val in _fields(plane):
            if pfn == 2 and pwt == 2:
                pname = bytes(val).decode("utf-8", "replace")
            elif pfn == 3 and pwt == 2:
                lname, busy = _line_busy_ps(val)
                if busy:
                    lines[lname] = lines.get(lname, 0) + busy
        if lines:
            out[pname] = lines
    return out


def _line_op_ps(line_buf: memoryview) -> Tuple[str, Dict[int, int]]:
    """(line_name, {metadata_id: summed duration_ps}) for one XLine."""
    name = ""
    per_md: Dict[int, int] = {}
    for fnum, wt, val in _fields(line_buf):
        if fnum == 2 and wt == 2:
            name = bytes(val).decode("utf-8", "replace")
        elif fnum == 4 and wt == 2:
            md = dur = 0
            for efn, ewt, ev in _fields(val):
                if efn == 1 and ewt == 0:
                    md = ev
                elif efn == 3 and ewt == 0:
                    dur = ev
            if dur > 0:
                per_md[md] = per_md.get(md, 0) + dur
    return name, per_md


def plane_op_ps(path: str) -> Dict[str, Dict[str, int]]:
    """{plane_name: {op_name: total duration_ps}} over "XLA Ops" lines.

    Op names come from the plane's event_metadata map (XPlane field 4:
    map<int64, XEventMetadata>, XEventMetadata{id=1, name=2}).  Durations
    are SUMMED per op (not interval-unioned): the per-op split is a
    where-does-the-time-go diagnostic, so overlap within one op name is
    attributed to it in full.
    """
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, Dict[str, int]] = {}
    for fnum, wt, plane in _fields(space):
        if fnum != 1 or wt != 2:
            continue
        pname = ""
        md_names: Dict[int, str] = {}
        op_lines: List[Dict[int, int]] = []
        for pfn, pwt, val in _fields(plane):
            if pfn == 2 and pwt == 2:
                pname = bytes(val).decode("utf-8", "replace")
            elif pfn == 3 and pwt == 2:
                lname, per_md = _line_op_ps(val)
                if lname == "XLA Ops" and per_md:
                    op_lines.append(per_md)
            elif pfn == 4 and pwt == 2:
                mid = 0
                mname = ""
                for mfn, mwt, mv in _fields(val):
                    if mfn == 1 and mwt == 0:
                        mid = mv
                    elif mfn == 2 and mwt == 2:
                        for efn, ewt, ev in _fields(mv):
                            if efn == 2 and ewt == 2:
                                mname = bytes(ev).decode("utf-8", "replace")
                md_names[mid] = mname
        if not op_lines:
            continue
        ops: Dict[str, int] = {}
        for per_md in op_lines:
            for mid, ps in per_md.items():
                nm = md_names.get(mid, f"metadata_{mid}")
                ops[nm] = ops.get(nm, 0) + ps
        out[pname] = ops
    return out


def device_op_seconds(logdir: str) -> Dict[str, float]:
    """{op_name: device-seconds} summed over all TPU planes in a trace
    dir — the op-level complement of :func:`device_busy_seconds`."""
    totals: Dict[str, float] = {}
    for path in find_xplane_files(logdir):
        for pname, ops in plane_op_ps(path).items():
            if "TPU" not in pname or "SparseCore" in pname:
                continue
            for nm, ps in ops.items():
                totals[nm] = totals.get(nm, 0.0) + ps / 1e12
    return totals


def find_xplane_files(logdir: str) -> List[str]:
    hits = []
    for root, _dirs, files in os.walk(logdir):
        for f in files:
            if f.endswith(".xplane.pb"):
                hits.append(os.path.join(root, f))
    return sorted(hits)


def device_busy_seconds(logdir: str) -> Dict[str, float]:
    """Per-device-plane busy seconds summed over that plane's op lines.

    Planes whose name contains "TPU" (e.g. ``/device:TPU:0``) are the
    accelerator timelines; ``/host:CPU`` planes carry runtime threads and
    are excluded.  Within a TPU plane, ONLY the "XLA Ops" line(s) carry
    executed kernels — every other line ("Steps", "XLA Modules",
    "#"-prefixed derived lines, future additions) aggregates or annotates
    those same intervals and would double-count them, so the filter is an
    allowlist, not a denylist.
    """
    totals: Dict[str, float] = {}
    for path in find_xplane_files(logdir):
        for pname, lines in plane_busy_ps(path).items():
            if "TPU" not in pname or "SparseCore" in pname:
                continue
            busy = 0
            for lname, ps in lines.items():
                if lname != "XLA Ops":
                    continue
                busy += ps
            if busy:
                totals[pname] = totals.get(pname, 0.0) + busy / 1e12
    return totals
