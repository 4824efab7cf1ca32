"""Open-loop request streams: arrival times and requesting users.

Copies of the program's generators (`repro.scheduling.workload`), changed
where a benchmark window needs it. Zipf users draw the same ranks as
`sample_users`, so a seed gives the program's users. A window of Poisson
traffic takes `poisson_fixed`: a fixed count of arrivals whose gaps are
one set of exponential draws, shuffled by the seed, so that a seed
reorders the work without changing its amount (the program draws the
count with the gaps). The on/off (Markov-modulated) process is
vectorised: each ON or OFF segment gets a Poisson count at its rate and
uniform positions inside it, which is the same point process as the
program's gap-by-gap loop (same distribution, other draws) at a cost that
holds for millions of arrivals.
"""
from __future__ import annotations

import numpy as np


def onoff(rate: float, seconds: float, rng: np.random.Generator, *,
          burst_factor: float, duty_cycle: float,
          period_s: float) -> np.ndarray:
    """Sorted arrival seconds in [0, seconds): each period is ON for
    ``duty_cycle`` of it at ``rate * burst_factor`` and OFF for the rest at
    the rate that keeps the long-run mean at ``rate``."""
    phi, b = duty_cycle, burst_factor
    assert 0.0 < phi < 1.0 and b * phi <= 1.0 + 1e-9, (phi, b)
    n_cycles = int(np.ceil(seconds / period_s))
    starts = np.arange(n_cycles) * period_s
    rate_off = rate * (1.0 - b * phi) / (1.0 - phi)
    seg_start = np.stack([starts, starts + phi * period_s], 1).reshape(-1)
    seg_len = np.tile([phi * period_s, (1.0 - phi) * period_s], n_cycles)
    seg_rate = np.tile([rate * b, rate_off], n_cycles)
    counts = rng.poisson(seg_rate * seg_len)
    t = (np.repeat(seg_start, counts)
         + rng.random(int(counts.sum())) * np.repeat(seg_len, counts))
    t.sort()
    return t[t < seconds]


def poisson_fixed(rate: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """(round(rate * seconds),) sorted arrival seconds in [0, seconds): the
    same set of exponential gaps for every seed (drawn from a fixed stream
    and scaled to fill the window), in the order ``rng`` shuffles them. A
    seed then changes the order of the work and not its amount."""
    n = max(int(round(rate * seconds)), 1)
    gaps = np.random.default_rng(0).exponential(1.0, n)
    gaps *= seconds / gaps.sum()          # the last gap runs to the window's end
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps[:-1]))])


def window_arrivals(traffic: dict, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Arrival seconds in [0, seconds) for a traffic file's ``arrivals``."""
    a = traffic["arrivals"]
    if a["process"] == "poisson":
        return poisson_fixed(float(a["rate_rps"]), seconds, rng)
    if a["process"] == "onoff":
        return onoff(float(a["rate_rps"]), seconds, rng,
                     burst_factor=float(a["burst_factor"]),
                     duty_cycle=float(a["duty_cycle"]),
                     period_s=float(a["period_s"]))
    raise ValueError(f"unknown arrival process {a['process']!r}")


def zipf_users(n: int, n_users: int, s: float,
               rng: np.random.Generator) -> np.ndarray:
    """(n,) user ids, p(rank) proportional to rank^-s over a seed-keyed
    permutation of the users."""
    ranks = rng.permutation(n_users)
    p = np.arange(1, n_users + 1, dtype=np.float64) ** (-s)
    p /= p.sum()
    return ranks[rng.choice(n_users, n, p=p)].astype(np.int64)


def window_users(traffic: dict, n: int, n_users: int,
                 rng: np.random.Generator) -> np.ndarray:
    u = traffic["users"]
    if u["dist"] == "zipf":
        return zipf_users(n, n_users, float(u["s"]), rng)
    if u["dist"] == "uniform":
        return rng.integers(0, n_users, n).astype(np.int64)
    raise ValueError(f"unknown user distribution {u['dist']!r}")
