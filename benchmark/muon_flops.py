"""A step's Newton-Schulz FLOPs under ``muon``, from a configuration's
shapes: the numerator of ``muon_ns_mxu_share``, and the expander that gives
the shapes (``buckets.expand_tensors`` gives sizes only).

Two counts a matrix, m the shorter side and n the longer:

- :func:`published`: five steps of ``X X^T`` (2 m^2 n), ``A A`` (2 m^3) and
  ``B X`` (2 m^2 n) as the published code multiplies them:
  ``5 * (4 m^2 n + 2 m^3)``;
- :func:`least`: ``X X^T`` and ``A A`` are symmetric, so half of each
  product is enough and no correct implementation needs more than
  ``5 * (3 m^2 n + m^3)``.  A share of the MXU's peak is taken of this
  count, so that none can read over 100%.
"""

from __future__ import annotations

import fnmatch
from typing import Dict, List, Sequence, Tuple

NS_STEPS = 5


def expand_shapes(entries: Sequence) -> List[Tuple[str, Tuple[int, int]]]:
    """``(name, (rows, cols))`` a tensor, in the order of the file, named
    as ``buckets.expand_tensors`` names them; a vector ``[n]`` is
    ``(1, n)``."""
    out: List[Tuple[str, Tuple[int, int]]] = []
    for entry in entries:
        if isinstance(entry, dict):
            for i in range(int(entry["repeat"])):
                out.extend((f"{entry['name']}.{i}.{name}", shape)
                           for name, shape in expand_shapes(entry["tensors"]))
        else:
            name, shape = entry
            dims = [int(d) for d in shape]
            if len(dims) > 2:
                raise ValueError(f"{name}: {shape} is no matrix or vector")
            out.append((str(name), (1, *dims)[-2:]))
    return out


def is_adamw(name: str, patterns: Sequence[str]) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


def matrices(config: dict) -> List[Tuple[int, int]]:
    """The ``(rows, cols)`` of the configuration's keys under Muon."""
    return [shape for name, shape in expand_shapes(config["tensors"])
            if not is_adamw(name, config["adamw_keys"])]


def published(shapes: Sequence[Tuple[int, int]]) -> float:
    return float(sum(NS_STEPS * (4 * min(s) ** 2 * max(s) + 2 * min(s) ** 3)
                     for s in shapes))


def least(shapes: Sequence[Tuple[int, int]]) -> float:
    return float(sum(NS_STEPS * (3 * min(s) ** 2 * max(s) + min(s) ** 3)
                     for s in shapes))


def by_group(shapes: Sequence[Tuple[int, int]]) -> Dict[Tuple[int, int], int]:
    """How many matrices of each ``(shorter, longer)`` side."""
    out: Dict[Tuple[int, int], int] = {}
    for s in shapes:
        key = (min(s), max(s))
        out[key] = out.get(key, 0) + 1
    return out
