"""Uniform lengths between ``min`` and ``max``."""
import numpy as np


def ppf(params: dict, q):
    """The lengths at quantiles ``q``, before rounding."""
    return params["min"] + (params["max"] - params["min"]) * np.asarray(q)
