"""95th percentile of how late the load generator submitted, in ms.

Measured on the host clock against each request's scheduled arrival, so
that a starved generator does not read as a fast engine.
"""

import numpy as np


def read(trace, record):
    lag = record.traced.get("arrival_lag_s")
    if lag is None or not len(lag):
        return None
    return float(np.percentile(lag, 95) * 1e3)
