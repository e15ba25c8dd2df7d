"""Percentiles, as the engine's SLO tracker (``serving/slo.py``
``_pct``) computes its tails: numpy's linear interpolation."""
import numpy as np


def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))
