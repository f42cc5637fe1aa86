"""What the readers ``layer_metrics/mixed_update_ms.py``,
``mixed_update_roofline.py`` and ``convert_ms.py`` share: the cell under
``lamb`` whose job dtype is narrower than its store's.

The two kernels are ``lamb_ops.py``'s, by the same names (a bf16 gradient
and a bf16 pulled tree are operands of the same custom calls,
``%lamb_moments.1 f32[rows,128]`` and ``%lamb_apply.1 f32[rows,128]``: the
store is the first result of both).  What this file adds is the count of
their least bytes with the job's sizes (``lamb_mixed_bytes.py``) and the
operations that must NOT be there: anything outside the two kernels whose
first result is as large as the tree, which is a pass (a ``convert``, a
``copy``, a ``pad``, a ``slice``) that the kernels were to make for nothing.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

import numpy as np

from buckets import expand_tensors
from lamb_bytes import over_vmem
from lamb_mixed_bytes import lamb_mixed_update
from lamb_ops import KERNELS, update_ms
from sparse_handle_ops import kind_and_shape

_DIMS = re.compile(r"^\w+\[([\d,]*)\]$")


def cell_sizes(config: dict) -> Optional[Dict[str, float]]:
    """Parameters, chips and the update's least bytes on one device; None
    for a configuration with no job dtype of its own."""
    if "job_dtype" not in config:
        return None
    sizes = [n for _, n in expand_tensors(config["tensors"])]
    W = int(config["chips"])
    import jax.numpy as jnp     # numpy alone does not know bfloat16

    job = jnp.dtype(config["job_dtype"]).itemsize
    own = jnp.dtype(config["dtype"]).itemsize
    return {"parameters": sum(sizes), "chips": W,
            "update_bytes": lamb_mixed_update(
                sum(sizes), W, over_vmem(sizes, W, own), job, own)}


def elements(shape: str) -> int:
    """``f32[2627072,128]`` -> 336,265,216; 1 for a scalar."""
    m = _DIMS.match(shape)
    dims = [int(d) for d in m.group(1).split(",") if d] if m else []
    return int(np.prod(dims, dtype=np.int64)) if dims else 1


def tree_sized_ms(ctx) -> Optional[float]:
    """Milliseconds a traced step in operations outside the two kernels
    whose first result holds at least as many elements as one device's
    share of the parameters, of any dtype.  0 where the program has the
    kernels and nothing else of that size; None where there is no trace of
    a device, the configuration has no job dtype or the program has no
    such kernels (nothing to read)."""
    sizes = cell_sizes(ctx.config)
    if sizes is None or update_ms(ctx) is None:
        return None
    least = sizes["parameters"] // sizes["chips"]
    seconds = 0.0
    for name, s in ctx.reduction.op_seconds.items():
        parts = kind_and_shape(name)
        if parts is None or parts[0] in KERNELS:
            continue
        if elements(parts[1]) >= least:
            seconds += s
    return seconds * 1e3 / ctx.reduction.steps
