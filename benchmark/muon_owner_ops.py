"""What the readers of a step under ``muon`` over several colocated servers
share (``layer_metrics/muon_owned_ns_ms.py``, ``muon_owned_ns_mxu_share.py``,
``muon_exchange_ms.py``, ``muon_exchange_ici_share.py``,
``muon_place_ms.py``, ``muon_owned_rest_ms.py``,
``muon_owned_rest_roofline.py``), beside ``muon_ops.py``, and the least
bytes of such a step on one chip.  Nothing here knows the program's plan:
the counts are what ANY deal of whole matrices to the owners has to do.

- The fullest owner's Newton-Schulz FLOPs: every matrix lies whole on one
  owner, so the fullest holds at least a W-th of the tree's FLOPs and at
  least the heaviest matrix (``fullest_owner_flops``, of ``muon_flops``'s
  least count).  An uneven deal runs more on its fullest owner and reads a
  LOWER share of the peak for it: the share prices the deal with the
  kernels.
- The fullest owner's Newton-Schulz time: a device plane at a time, the
  operations ``muon_ops.is_ns`` tells (``fullest_ns_ms``); the mean over
  the chips would hide the owner the step waits for.
- The least bytes of a step on one chip: ``least_bytes_a_chip``.
- A step on a chip is one program in three parts, told by where the
  program's COLLECTIVES lie in it (``sparse_route_ops.is_collective``: by
  opcode), whatever compiles the parts: before the first collective starts
  the worker's row is laid into the owners' order, between the first and the
  last the owner updates its keys, after the last ends the gathered tree is
  laid back into key order (``step_parts``).  The placing passes are the
  first and the third part (``placing_ms``: XLA compiles them to some two
  hundred copies of many names and shapes, and a trace hands an operation's
  name and times and no scope); the owner's passes outside Newton-Schulz are
  the second part less its collectives and its Newton-Schulz operations
  (``fullest_rest_ms``).  A program that hides the exchange behind the
  products would move what it overlaps out of the first and third parts,
  and the reader would have to learn its order.
"""

from __future__ import annotations

from typing import Dict, Optional

from muon_flops import least, matrices
from muon_ops import cell_sizes, is_ns, rest_bytes
from sparse_handle_ops import kind_and_shape
from sparse_route_ops import is_collective
from trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, short_name,
                          total, union)


def fullest_owner_flops(config: dict) -> Optional[float]:
    """The least Newton-Schulz FLOPs the fullest of ``chips`` owners runs a
    step; None for a configuration that is not under ``muon``."""
    if not str(config.get("server_handle", "")).startswith("muon"):
        return None
    shapes = matrices(config)
    return max(least(shapes) / int(config["chips"]),
               max(least([s]) for s in shapes))


def least_bytes_a_chip(muon_values: int, adamw_values: int, workers: int,
                       itemsize: int = 4) -> Dict[str, float]:
    """One bulk-synchronous push_pull of the tree on ``workers`` colocated
    chips, state sharded by owner, per chip.

    HBM: the worker's own gradient row read (``itemsize * N``), the part of
    the pulled tree it did not own written (``itemsize * N * (W-1)/W``), and
    the owner's share of ``muon_ops.rest_bytes`` (its keys' summed gradient
    read, its state and parameters read and written, its part of the tree
    written).
    ICI: the reduction to the owners and the gather back each move
    ``N * (W-1)/W`` values in and out of every chip
    (``least_bytes.dense_adam_step``'s convention).

    Left out: every placing copy, padding, X and O, any temporary."""
    n, w = float(muon_values + adamw_values), float(workers)
    return {
        "hbm": (itemsize * n + itemsize * n * (w - 1) / w
                + rest_bytes(muon_values, adamw_values, itemsize) / w),
        "ici": 2 * itemsize * n * (w - 1) / w,
    }


def fullest_ns_ms(ctx) -> Optional[float]:
    """Device milliseconds a traced step in Newton-Schulz operations on the
    chip that spends the most there; None where there is no trace of a
    device, the configuration is not under ``muon`` or no chip ran one."""
    if ctx.reduction is None or ctx.profile is None \
            or not ctx.reduction.steps:
        return None
    sizes = cell_sizes(ctx.config)
    if sizes is None:
        return None
    per_device = []
    for plane in ctx.profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ns = 0.0
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            ns += sum(float(ev.duration_ns) for ev in line.events
                      if _is_ns(ev.name, sizes))
        per_device.append(ns)
    if not per_device or not max(per_device):
        return None
    return max(per_device) / ctx.reduction.steps / 1e6


def step_parts(ctx):
    """``(placing, rest)`` nanoseconds of the traced section for each chip
    that ran a program with a collective in it; None where there is no
    trace of a device, the configuration is not under ``muon`` or no
    program has a collective (one chip).  A program at a time (the
    ``XLA Modules`` line): ``placing`` is the union of the operations that
    end before its first collective starts or start after its last one
    ends; ``rest`` the union of the operations in between that are no
    collective, less the union of those told as Newton-Schulz (an operation
    the trace shows both whole and by its parts is counted once)."""
    if ctx.reduction is None or ctx.profile is None \
            or not ctx.reduction.steps:
        return None
    sizes = cell_sizes(ctx.config)
    if sizes is None:
        return None
    per_device = []
    for plane in ctx.profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        events, programs = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                events += [(float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns), ev.name)
                           for ev in line.events]
            elif line.name == MODULES_LINE:
                programs += [(float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
                             for ev in line.events]
        placing = rest = 0.0
        found = False
        for lo, hi in programs:
            ops = [e for e in events if lo <= e[0] < hi]
            moved = [(s, e) for s, e, name in ops if is_collective(name)]
            if not moved:
                continue
            found = True
            first = min(s for s, _ in moved)
            last = max(e for _, e in moved)
            placing += total(union((s, e) for s, e, _ in ops
                                   if e <= first or s >= last))
            inside = [(s, e, name) for s, e, name in ops
                      if not (e <= first or s >= last)
                      and not is_collective(name)]
            ns = [(s, e) for s, e, name in inside if _is_ns(name, sizes)]
            rest += total(union((s, e) for s, e, _ in inside)) - total(
                union(ns))
        if found:
            per_device.append((placing, rest))
    return per_device or None


def _is_ns(name: str, sizes) -> bool:
    parts = kind_and_shape(short_name(name))
    return parts is not None and is_ns(parts[1], sizes["groups"])


def placing_ms(ctx) -> Optional[float]:
    """Device milliseconds a traced step in the two placing passes, the
    mean over the chips (each lays its own worker's row and its own copy of
    the tree); None where :func:`step_parts` reads nothing."""
    parts = step_parts(ctx)
    if parts is None:
        return None
    return (sum(p for p, _ in parts) / len(parts) / ctx.reduction.steps
            / 1e6)


def fullest_rest_ms(ctx) -> Optional[float]:
    """Device milliseconds a traced step in the owner's passes outside
    Newton-Schulz (the summed gradient cut to the owner's keys, momentum,
    Nesterov and the cast, decay and step, AdamW, a branch an owner takes
    for the keys left over) on the chip that spends the most there; None
    where :func:`step_parts` reads nothing."""
    parts = step_parts(ctx)
    if parts is None:
        return None
    return max(r for _, r in parts) / ctx.reduction.steps / 1e6
