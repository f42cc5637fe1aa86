"""Device mesh construction.

The PS roles map onto mesh axes instead of RDMA endpoints (SURVEY §2.9):
the ``kv`` axis carries both the worker fan-in (gradient reduction) and the
server sharding (key-range ownership) — the JOINT/colocated deployment of
the reference (``ps.h:59-76``), which is the natural fit for a TPU slice.
Model-parallel axes (dp/sp/tp) for the model zoo are built with
:func:`make_mesh`.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def default_mesh(axis_name: str = "kv", num_devices: Optional[int] = None):
    """1-D mesh over all (or the first ``num_devices``) local devices."""
    import jax

    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), (axis_name,))


def make_mesh(shape: Sequence[int], axis_names: Tuple[str, ...]):
    """N-D mesh with the given per-axis sizes (product must divide the
    available device count)."""
    import jax
    from jax.sharding import Mesh

    n = int(np.prod(shape))
    devices = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devices, tuple(axis_names))
