"""What every traffic generator shares.  A mix is a JSON file of parameters
under ``bench/traffic/``; its ``generator`` names a module beside it
(``bench/traffic/<generator>.py``) that turns the mix and a seed into the
inputs the program receives.  A new arrival law is one new module there.

Every seed gets the same multiset of sizes and inter-arrival gaps: each is
a stratified set of quantiles of the mix's distribution, and the seed only
chooses their order and the token ids.  The order is blocked: the values
are split into runs of ``order_block`` with sums as even as can be, the
same runs for every seed, so every stretch of the window carries about the
same load and two seeds differ in which request comes when within a
stretch, not in how the work bunches across the window.

A serving generator's ``serve_requests(mix, seed, seconds, vocab)`` gives
(warm requests, window requests in order of arrival).  The mix's ``warm``
block gives the requests that fill the running batch before the window:
an in-flight request of an open loop is caught part way through its
output, so warm outputs are size-biased and cut at a uniform point.  A
training generator's ``train_batch(mix, seed, step, rows, vocab)`` gives
each step rows of its own.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    due_s: float            # seconds after the window opens (warm: 0)
    prompt: np.ndarray      # (S,) int32 token ids
    max_new: int


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, *, size_biased: bool = False) -> np.ndarray:
    """``n`` stratified lengths of a clipped lognormal, ascending.

    ``spec``: {"dist": "lognormal", "median", "sigma", "min", "max"}.
    ``size_biased`` draws from the distribution weighted by length (the
    lengths an in-flight request has), a lognormal with mu + sigma^2."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu = math.log(spec["median"])
    sigma = float(spec["sigma"])
    if size_biased:
        mu += sigma * sigma
    z = np.array([_NORMAL.inv_cdf(u) for u in quantiles(n)])
    x = np.rint(np.exp(mu + sigma * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def blocked(r: np.random.Generator, values: np.ndarray, block: int
            ) -> np.ndarray:
    """``values`` reordered in runs of ``block`` whose sums are as even as
    a greedy split makes them: the largest value left goes to the run with
    the least sum that has room.  The runs are the same for every seed;
    ``r`` orders the full runs and the values within each, and a short
    run, where ``block`` does not divide the values, comes last."""
    values = np.sort(np.asarray(values))[::-1]
    n = len(values)
    runs = -(-n // block)
    room = np.full(runs, block)
    room[-1] = n - block * (runs - 1)
    sums = np.zeros(runs)
    members: list[list] = [[] for _ in range(runs)]
    for v in values:
        i = int(np.argmin(np.where(room > 0, sums, np.inf)))
        members[i].append(v)
        sums[i] += v
        room[i] -= 1
    order = [*r.permutation(runs - 1), runs - 1]
    return np.concatenate([r.permutation(members[i]) for i in order])


def tokens(r: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return r.integers(0, vocab, size=int(n), dtype=np.int32)


def open_loop(mix: dict, seed: int, due: np.ndarray, vocab: int
              ) -> tuple[list[ServeRequest], list[ServeRequest]]:
    """(warm requests, window requests) for arrivals at ``due`` (seconds
    after the window opens, ascending), sizes from the mix's
    ``prompt_tokens`` and ``output_tokens``."""
    n = len(due)
    block = int(mix["order_block"])
    order = rng(seed, 6)
    prompts = blocked(order, lengths(mix["prompt_tokens"], n), block)
    outputs = blocked(order, lengths(mix["output_tokens"], n), block)
    ids = rng(seed, 2)
    window = [ServeRequest(float(due[i]), tokens(ids, prompts[i], vocab),
                           int(outputs[i])) for i in range(n)]
    m = int(mix["warm"]["requests"])
    wrng = rng(seed, 3)
    wprompts = wrng.permutation(lengths(mix["prompt_tokens"], m))
    # size-biased outputs, each cut at a point paired with it the same way
    # for every seed (so every seed gets the same remaining lengths)
    cut = rng(0, 3).permutation(quantiles(m))
    left = np.maximum(np.ceil(lengths(mix["output_tokens"], m,
                                      size_biased=True) * cut), 1)
    left = wrng.permutation(left.astype(np.int64))
    warm = [ServeRequest(0.0, tokens(ids, wprompts[i], vocab), int(left[i]))
            for i in range(m)]
    return warm, window
