"""The least bytes LAMB has to move where the job's dtype is narrower than
the store's (bf16 gradients in, f32 p, m, v, bf16 parameters out): the
numerators of ``roofline_share`` and ``mixed_update_roofline`` in the cell
under ``lamb`` with a ``job_dtype``.

As in ``lamb_bytes.py``, a count holds only what any correct implementation
must move on one device, so that a share of the roofline cannot pass 100%.
Two things differ from the f32 count.  The job's side is ``job_itemsize``
an element: its gradient is read once as it came and its parameters are
written once as it takes them.  And that write is counted: where one shard
holds the bucket the pulled tree is a buffer of its own that somebody has
to fill (PR 38 moved that write into the second kernel, and a count that
leaves it out reads a step that does it against a step that need not).
"""

from __future__ import annotations

from typing import Dict


def lamb_mixed_update(params: int, workers: int, over: int,
                      job_itemsize: int = 2, itemsize: int = 4) -> float:
    """HBM bytes of the update itself on one device, whatever the number of
    kernels: read the gradient of its shard (on one device the job's own,
    ``job_itemsize`` an element; over several the sum over W, which is
    ``itemsize``), read and write p, m and v (``6 * itemsize``), on one
    device write the pulled parameters (``job_itemsize``; over several
    they are the all-gather's, counted by :func:`dense_lamb_mixed_step`),
    and for the ``over`` elements of keys larger than VMEM
    (``lamb_bytes.over_vmem``, the f32 count's own rule) a second pass
    (``3 * itemsize``)."""
    n, w = float(params), float(workers)
    one = workers == 1
    grad = job_itemsize if one else itemsize
    pulled = job_itemsize if one else 0
    return ((grad + 6 * itemsize + pulled) * n
            + 3 * itemsize * float(over)) / w


def dense_lamb_mixed_step(params: int, workers: int, over: int = 0,
                          job_itemsize: int = 2, itemsize: int = 4
                          ) -> Dict[str, float]:
    """One bulk-synchronous push_pull of ``params`` parameters under LAMB
    with a narrower job dtype on ``workers`` devices, per device.

    HBM: read the device's own gradient (``job_itemsize * N``), read and
    write p, m and v of its shard (``6 * itemsize * N / W``), write the
    whole pulled tree (``job_itemsize * N``: its own shard's part too, a
    fresh buffer of the job's dtype that the store cannot stand in for),
    and the second pass over keys larger than VMEM.  ICI: a reduce-scatter
    and an all-gather of ``job_itemsize`` values (the least: sums need f32
    only where they are added).

    Left out: the f32 sum's own traffic over several devices, padding, the
    step slot, the norms (3 KB), any temporary."""
    n, w = float(params), float(workers)
    hbm = (2 * job_itemsize * n + 6 * itemsize * n / w
           + 3 * itemsize * float(over) / w)
    ici = 2 * job_itemsize * n * (w - 1) / w
    return {"hbm": hbm, "ici": ici}
