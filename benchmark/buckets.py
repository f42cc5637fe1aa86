"""Tensor lists and the bucket rule.

A dense configuration file lists its gradient tree as data::

    "tensors": [["embeddings.word", [30522, 1024]],
                {"repeat": 24, "name": "layer", "tensors": [["q.w", [1024, 1024]], ...]},
                ["pooler.w", [1024, 1024]]]

``expand_tensors`` flattens that to ``(name, elements)`` in order and
``make_buckets`` cuts the stream into buckets.  The rule is copied from
``pslite_tpu/models/resnet_trace.py::make_buckets`` (BytePS's
BYTEPS_PARTITION_BYTES semantics): small tensors fuse in order until the
next would overflow the limit, larger ones split into limit-sized pieces.
"""

from __future__ import annotations

from math import prod
from typing import Iterable, List, Sequence, Tuple


def expand_tensors(entries: Sequence) -> List[Tuple[str, int]]:
    """``(name, element count)`` per tensor, in the order of the file."""
    out: List[Tuple[str, int]] = []
    for entry in entries:
        if isinstance(entry, dict):
            for i in range(int(entry["repeat"])):
                out.extend(
                    (f"{entry['name']}.{i}.{name}", n)
                    for name, n in expand_tensors(entry["tensors"])
                )
        else:
            name, shape = entry
            out.append((str(name), prod(int(d) for d in shape)))
    return out


def make_buckets(sizes: Iterable[int], limit: int) -> List[int]:
    """Element counts of the buckets that ``sizes`` (element counts of the
    tensors, in order) are cut into, at most ``limit`` elements each."""
    buckets: List[int] = []
    cur = 0
    for n in sizes:
        while n >= limit:
            if cur:
                buckets.append(cur)
                cur = 0
            buckets.append(limit)
            n -= limit
        if cur + n > limit:
            buckets.append(cur)
            cur = 0
        cur += n
    if cur:
        buckets.append(cur)
    return buckets
