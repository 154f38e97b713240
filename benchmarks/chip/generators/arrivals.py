"""Open-loop arrival schedules.

`schedule` gives ``n`` arrival times at ``rate`` a second whose gaps are
the ``n`` quantiles of the exponential distribution at ``(i + 0.5) / n``:
the gaps of a Poisson process, with the same set of gaps for every seed
and only their order drawn from it.  So every seed offers the same load
over the same span, and only where the bursts fall changes.
"""

from __future__ import annotations

import numpy as np


def schedule(rng: np.random.Generator, n: int, rate: float) -> np.ndarray:
    """(n,) ascending arrival times in seconds, the first at 0."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
