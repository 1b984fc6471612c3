"""Open-loop Poisson arrivals at the mix's ``arrivals.rate_per_s``, with
lognormal prompt and output lengths (``bench/trafficlib.py``).

Over a window of ``seconds`` the mix sends ``rate x seconds`` requests
whose gaps are that many stratified quantiles of the exponential law, in
the blocked order of ``order_block``."""
from __future__ import annotations

import numpy as np

import trafficlib


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int):
    rate = float(mix["arrivals"]["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-trafficlib.quantiles(n)) / rate
    due = np.cumsum(trafficlib.blocked(trafficlib.rng(seed, 1), gaps,
                                       int(mix["order_block"])))
    return trafficlib.open_loop(mix, seed, due, vocab)
