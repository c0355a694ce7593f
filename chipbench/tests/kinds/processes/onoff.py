"""An arrival process for the tests: on-off bursts.

One modulation is shared by every spout stream: a slot is "on" with
probability ``p_on`` and then offers ``ratio`` times what an "off" slot
offers, scaled so that each stream's mean stays its rate. Counts are
Poisson around it.
"""
import numpy as np


def draw(rng, rates: np.ndarray, T: int, ratio: float, p_on: float) -> np.ndarray:
    """(T, I, C) float32 tuple counts, drawn on the nonzero streams only."""
    on = rng.random(T) < p_on
    level = np.where(on, ratio, 1.0) / (p_on * ratio + 1.0 - p_on)
    rows, cols = np.nonzero(rates)
    out = np.zeros((T,) + rates.shape, np.float32)
    out[:, rows, cols] = rng.poisson(level[:, None] * rates[rows, cols][None, :])
    return out
