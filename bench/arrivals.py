"""The one generator of job streams: arrival times and job shapes from a
traffic mix's parameters and a seed.

Every seed gets the same work in another order: the gaps between
arrivals are the ``n`` midpoint quantiles of the exponential distribution
at the mix's rate (a Poisson stream's gaps, with the sampling noise taken
out), and the shapes are the mix's sweep counts and swap cadences in
equal shares with (workload, region) pairs drawn uniformly by a fixed
generator. The seed permutes the gaps and, separately, the shapes.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def schedule(traffic: dict, n_workloads: int, n_regions: int,
             seconds: float, seed: int
             ) -> Tuple[np.ndarray, List[Tuple[int, int, int, int]]]:
    """``(due seconds from the window start, [(workload, region, sweeps,
    swap_every)])`` for the jobs due in a window of ``seconds``."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng([seed, 4])
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    sweeps = np.resize(np.asarray(traffic["sweeps"]), n)
    swaps = np.resize(np.asarray(traffic["swap_every"]), n)
    fixed = np.random.default_rng(0)
    wi = fixed.integers(0, n_workloads, n)
    ri = fixed.integers(0, n_regions, n)
    order = rng.permutation(n)
    shapes = [(int(wi[k]), int(ri[k]), int(sweeps[k]), int(swaps[k]))
              for k in order]
    return due, shapes
