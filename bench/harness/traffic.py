"""The general traffic generator: open-loop arrivals from a mix's
parameters and the seed.

Every seed gets the same work in another order: the inter-arrival gaps
are stratified quantiles of their distribution (one per request),
shuffled by the seed. So runs with different seeds differ in order, not
in load.

* arrivals: a Poisson process at ``rate_per_s`` — exponential gaps.

What each request holds comes from the configuration's data generator
(``kinds/serve.histories``).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from harness import seeds


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def arrivals(mix: Dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of the requests that
    fall inside the window."""
    rate = mix["rate_per_s"]
    n = int(round(rate * seconds))
    gaps = -np.log1p(-_strata(n)) / rate
    seeds.rng(seed, 1).shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    return due[due < seconds]
