"""Median gap between consecutive tokens of one request, both tokens
inside the window, over all requests (milliseconds, host clock)."""

import numpy as np


def read(ctx):
    gaps = ctx.itl_gaps()
    return float(np.percentile(gaps, 50)) * 1e3 if gaps.size else None
