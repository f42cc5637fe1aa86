"""Which device operations of a step under ``lamb`` the readers
``layer_metrics/lamb_update_ms.py``, ``lamb_update_roofline.py`` and
``lamb_norm_ms.py`` count, worked out from the sizes of the cell that is
read (``ctx.config``: the cell's own configuration file).

A reader is given ``Reduction.op_seconds`` (``sparse_handle_ops.py`` says
what a short name is and how kind and shape are taken from it):

- the two kernels are custom calls that carry their names into the trace,
  ``%lamb_moments.1 f32[rows,128]`` and ``%lamb_apply.1 f32[rows,128]``:
  kinds ``lamb_moments`` and ``lamb_apply``;
- between them the program makes the keys' norms and ratios from the
  partial sums the first kernel leaves: operations whose first result has
  one or two numbers a key, ``f32[K]``, ``f32[K,2]`` or ``f32[2K]`` (the
  reshape of the partial sums, on more than one chip their all-reduce, the
  fusion that ends in ``lr * r_k``), K the number of tensors.
"""

from __future__ import annotations

from typing import Dict, Tuple

from buckets import expand_tensors
from lamb_bytes import lamb_update, over_vmem
from sparse_handle_ops import ms_a_step

KERNELS = ("lamb_moments", "lamb_apply")


def norm_shapes(n_keys: int) -> Tuple[str, ...]:
    return (f"f32[{n_keys}]", f"f32[{n_keys},2]", f"f32[{2 * n_keys}]")


def cell_sizes(config: dict) -> Dict[str, float]:
    """Keys, chips and the update's least bytes on one device."""
    sizes = [n for _, n in expand_tensors(config["tensors"])]
    W = int(config["chips"])
    return {"keys": len(sizes), "chips": W,
            "update_bytes": lamb_update(sum(sizes), W, over_vmem(sizes, W))}


def update_ms(ctx):
    """Milliseconds a traced step in the two kernels; None where there is
    no trace of a device or the program has no such kernel."""
    return ms_a_step(ctx, lambda kind, shape: kind in KERNELS)
