"""Arrival schedules: pure functions of the traffic mix, the seed and the
window length.

An open-loop Poisson mix at rate ``r`` over ``T`` seconds offers exactly
``round(r * T)`` requests, their due times uniform order statistics on
``[0, T)``: a Poisson process conditioned on its count.  Every seed thus
offers the same amount of work, in another order.  Each request asks for
one query drawn uniformly from the held-out pool.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Schedule(NamedTuple):
    due: np.ndarray        # (n,) seconds after the window opens, ascending
    pool_idx: np.ndarray   # (n,) query of each request


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose)."""
    words = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed)] + words))


def rate_qps(traffic: dict, config: dict) -> float:
    """The offered rate: a fixed rate, or a share of the config's knee."""
    rate = traffic["rate"]
    if "qps" in rate:
        return float(rate["qps"])
    return float(rate["knee_share"]) * float(config["knee_qps"])


def poisson(seed: int, rate: float, seconds: float, pool: int) -> Schedule:
    g = rng(seed, "arrivals")
    n = int(round(rate * seconds))
    due = np.sort(g.uniform(0.0, seconds, n))
    return Schedule(due=due, pool_idx=g.integers(0, pool, n))


def query_stream(seed: int, n: int, pool: int) -> np.ndarray:
    """Pool indices for a closed loop's successive requests."""
    return rng(seed, "closed").integers(0, pool, n)


def open_schedule(traffic: dict, config: dict, seed: int,
                  seconds: float) -> Schedule:
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    return poisson(seed, rate_qps(traffic, config), seconds,
                   int(config["data"]["pool"]))
