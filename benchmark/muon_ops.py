"""Which device operations of a step under ``muon`` are Newton-Schulz and
which are the handle's other passes, worked out from the sizes of the cell
that is read (``ctx.config``: the cell's own configuration file), and the
least HBM bytes of those other passes.

A reader is given ``Reduction.op_seconds`` (``sparse_handle_ops.py`` says
what a short name is and how kind and shape are taken from it).  The
program names its four parts by scope (``ps.update.muon.momentum``, ``.ns``,
``.apply``, ``.adamw``), which a device trace keeps in an operation's
metadata and ``op_seconds`` does not; what it keeps is an operation's first
result, and that tells the parts apart, whatever compiles them:

- between the cast that ends the momentum pass and the widening that
  begins the apply pass every value is bfloat16, so an operation inside the
  Newton-Schulz steps (a product with its epilogue, an epilogue alone, the
  normalisation) leaves a batch of bfloat16 matrices: ``bf16[B, m, n]``
  (X) or ``bf16[B, m, m]`` (A and B), (m, n) a ``(shorter, longer)`` side
  of the configuration's matrices and B at most the matrices of that side
  (the compiler may cut a batch);
- everything else a step runs is the rest: the gradient cut into matrices,
  momentum and Nesterov and the cast (an f32 momentum is its first
  result), decay and step, AdamW, the pulled tree, every layout change
  between a flat store and a batch of matrices.  The norms a matrix,
  ``f32[B]``, are a few microseconds and stay with the rest.

A program that keeps X in another type between its products would fall
silent here (``split_ms`` reads nothing where no operation is told as
Newton-Schulz), and the reader would have to learn its shapes.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from muon_flops import (by_group, expand_shapes, is_adamw, least, matrices,
                        published)
from sparse_handle_ops import kind_and_shape

_SHAPE = re.compile(r"^(\w+)\[([\d,]*)\]$")


def cell_sizes(config: dict) -> Optional[Dict[str, object]]:
    """Matrices by side, FLOPs and the rest's least bytes; None for a
    configuration that is not under ``muon``."""
    if not str(config.get("server_handle", "")).startswith("muon"):
        return None
    shapes = matrices(config)
    muon_values = sum(r * c for r, c in shapes)
    adamw_values = sum(
        r * c for name, (r, c) in expand_shapes(config["tensors"])
        if is_adamw(name, config["adamw_keys"]))
    return {
        "groups": by_group(shapes), "matrices": len(shapes),
        "published_flops": published(shapes), "least_flops": least(shapes),
        "rest_bytes": rest_bytes(muon_values, adamw_values),
    }


def rest_bytes(muon_values: int, adamw_values: int, itemsize: int = 4
               ) -> float:
    """HBM bytes any implementation moves outside the products on the one
    device that holds the bucket: the gradient read, M read and written, p
    read and written and the pulled tree written for a Muon value (6
    streams); the gradient read, m, v and p read and written and the
    pulled tree written for an AdamW value (8 streams).

    Left out: X written and read (2 B a Muon value each way, which a
    product's prologue could take from g and M itself), O, every layout
    change between the flat store and a batch of matrices, the step slot."""
    return float(itemsize * (6 * muon_values + 8 * adamw_values))


def is_ns(shape: str, groups) -> bool:
    """Whether an operation whose first result is ``shape`` lies inside
    the Newton-Schulz steps (the docstring above)."""
    m = _SHAPE.match(shape)
    if not m or m.group(1) != "bf16":
        return False
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    if len(dims) == 2:
        dims = (1, *dims)
    if len(dims) != 3:
        return False
    b, rows, cols = dims
    for (short, long_), count in groups.items():
        if b <= count and (rows, cols) in ((short, long_), (short, short)):
            return True
    return False


def split_ms(ctx) -> Optional[Tuple[float, float]]:
    """(Newton-Schulz ms, the rest's ms) a traced step; None where there
    is no trace of a device, the configuration is not under ``muon`` or the
    program ran no Newton-Schulz operation (a checkout without the
    handle)."""
    if ctx.reduction is None or not ctx.reduction.steps:
        return None
    sizes = cell_sizes(ctx.config)
    if sizes is None:
        return None
    ns = rest = 0.0
    for name, seconds in ctx.reduction.op_seconds.items():
        parts = kind_and_shape(name)
        if parts is not None and is_ns(parts[1], sizes["groups"]):
            ns += seconds
        else:
            rest += seconds
    if ns == 0.0:
        return None
    steps = ctx.reduction.steps
    return ns * 1e3 / steps, rest * 1e3 / steps
