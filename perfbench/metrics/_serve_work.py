"""Operations and bytes of the served requests, from their shapes: the
least any implementation has to do. Per request with c candidates (its
home city's POIs; none for a popularity slate) and factor width K: read
u (4K B), the c rows of v = p + q (4cK B) and c seen bits (c/8 B), and
compute c dot products (2cK operations)."""
import numpy as np


def served(x):
    """Candidate counts of the requests served in the traced window."""
    ok = ~np.isnan(x["done"])
    return x["candidates"][ok]


def work(x) -> tuple[float, float]:
    c = served(x).astype(np.float64)
    K = x["dim"]
    flops = 2.0 * K * c.sum()
    nbytes = 4.0 * K * len(c) + 4.0 * K * c.sum() + c.sum() / 8.0
    return float(flops), float(nbytes)
