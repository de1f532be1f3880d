"""Lognormal lengths: ``median`` and the log's standard deviation
``sigma``."""
from statistics import NormalDist

import numpy as np


def ppf(params: dict, q):
    """The lengths at quantiles ``q``, before rounding and clipping."""
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    return params["median"] * np.exp(params["sigma"] * z)
