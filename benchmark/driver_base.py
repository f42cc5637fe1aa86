"""What the drivers under ``drivers/`` share, for a new driver to import:
the type of a compared number, how many steps from the fresh state are
checked, the spans of a traced step, and a ``jax.random`` key from any seed.
"""

from __future__ import annotations

import contextlib
from typing import Tuple

# (name, value, limit): each number compared, beside its limit.
Comparison = Tuple[str, float, float]

CHECKED_STEPS = 3


class _Driver:
    """What the harness asks of a driver, and the spans of a traced step."""

    tracing = False
    steps_done = 0

    def _span(self, name: str):
        """A ``TraceAnnotation`` while the profiler runs, else nothing."""
        if self.tracing:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


def _jax_key(seed: int):
    """A key from any non-negative seed, also one past 32 signed bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
