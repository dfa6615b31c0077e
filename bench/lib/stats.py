"""Small helpers the metric readers share."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The ``q``-th percentile (linear interpolation), or None when there
    is nothing to read."""
    values = list(values)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), q))


def in_window(run, t: float) -> bool:
    return run.window[0] <= t < run.window[1]


def window_tokens(run) -> list:
    """(request, delivery times inside the window) of each request that
    delivered a token inside it."""
    out = []
    for r in run.requests:
        times = [t for t in r["delivered"] if in_window(run, t)]
        if times:
            out.append((r, times))
    return out
