"""Poisson arrivals: independent exponential gaps at the cell's rate."""
OPEN = True


def gaps(params: dict, rate_per_s: float, rng, n: int):
    """``n`` gaps between arrivals, in seconds, drawn from ``rng``."""
    return rng.exponential(1.0 / rate_per_s, n)
