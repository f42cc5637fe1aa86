"""The least bytes LAMB has to move: the numerators of ``roofline_share``
and ``lamb_update_roofline`` in the cell under ``lamb``.

As in ``least_bytes.py``, a count holds only what any correct implementation
must move on one device, so that a share of the roofline cannot pass 100%.
LAMB differs from Adam in one thing: no element of a key may be written
before the norm of the key's whole ``u`` is known.  A key whose ``p`` and
``u`` fit in VMEM together could still be read once and written once, in
one pass, so only a key that is larger than that is charged a second pass.
"""

from __future__ import annotations

from typing import Dict, Iterable

# One v5e TensorCore's VMEM (Google Cloud TPU v5e documentation: 128 MiB).
VMEM_BYTES = 128 * 2 ** 20


def over_vmem(key_sizes: Iterable[int], workers: int, itemsize: int = 4
              ) -> int:
    """Elements in keys of which one device's share of ``p`` and ``u``
    (two values an element, the least a second pass has to see) does not
    fit in VMEM, even with nothing else there."""
    return sum(int(n) for n in key_sizes
               if 2 * itemsize * int(n) / workers > VMEM_BYTES)


def lamb_update(params: int, workers: int, over: int, itemsize: int = 4
                ) -> float:
    """HBM bytes of the update itself on one device, whatever the number of
    kernels: read the summed gradient, read and write p, m and v of its
    shard (``7 * itemsize * N / W``, Adam's count), and for the ``over``
    elements of keys larger than VMEM (:func:`over_vmem`) a second pass:
    what holds ``u`` between the passes is written and read, or m and v
    are read again (``2 * itemsize`` an element either way), and p is read
    again (``itemsize``).

    Left out: the second pass over every key that would fit in VMEM (today's
    two kernels make it over all of them: 40 B an element where this counts
    28), the keys' borders and flags, the partial sums, padding."""
    return (7 * itemsize * float(params) + 3 * itemsize * float(over)
            ) / float(workers)


def dense_lamb_step(params: int, workers: int, over: int = 0,
                    itemsize: int = 4) -> Dict[str, float]:
    """One bulk-synchronous push_pull of ``params`` parameters under LAMB
    on ``workers`` devices, per device: ``least_bytes.dense_adam_step``'s
    count (the device's own gradient row, p, m and v of its shard read and
    written, the gathered parameters it did not own written; a
    reduce-scatter and an all-gather over ICI) plus the second pass over
    the ``over`` elements of keys larger than VMEM (:func:`lamb_update`),
    and over ICI the all-reduce of two norms a key, which is left out
    (3 KB).

    Left out as well: the copy of the device's own shard into the pulled
    array, padding, the step slot, any temporary."""
    n, w = float(params), float(workers)
    hbm = (itemsize * n + 6 * itemsize * n / w
           + itemsize * n * (w - 1) / w + 3 * itemsize * float(over) / w)
    ici = 2 * itemsize * n * (w - 1) / w
    return {"hbm": hbm, "ici": ici}
