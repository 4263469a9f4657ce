"""Wall-clock timing harness (the paper's install-time "timing program").

Times a zero-argument callable with warmup + best-of-k repeats.  JAX arrays
are synchronised via ``block_until_ready`` (the callable is responsible for
returning its output so we can block on it).
"""

from __future__ import annotations

import time
from typing import Callable

import numpy as np

__all__ = ["time_callable", "median_time"]


def _block(x) -> None:
    # a failed device computation raises here: a timing must never quietly
    # measure the enqueue instead (leaves that are not jax arrays pass)
    import jax
    jax.block_until_ready(x)


def time_callable(fn: Callable[[], object], *, warmup: int = 1,
                  repeats: int = 3, min_time_s: float = 0.0) -> float:
    """Median wall-clock seconds of ``fn`` over ``repeats`` runs."""
    for _ in range(warmup):
        _block(fn())
    times = []
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        _block(fn())
        dt = time.perf_counter() - t0
        times.append(dt)
        if min_time_s and sum(times) > min_time_s and len(times) >= 2:
            break
    return float(np.median(times))


def median_time(fn: Callable[[], object], **kw) -> float:
    return time_callable(fn, **kw)
