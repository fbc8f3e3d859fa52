"""95th percentile of the gaps between consecutive tokens of one request,
both inside the window (milliseconds, host clock).  Stalls land here: a
prefill chunk inside a decode step, a plan rebuild, a compile."""

import numpy as np


def read(ctx):
    gaps = ctx.itl_gaps()
    return float(np.percentile(gaps, 95)) * 1e3 if gaps.size else None
