"""The one traffic generator: reads a mix (``bench/traffic/<name>.json``)
and a cell's rate, and draws requests from ``--seed``.

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps (fixed quantiles of the mix's distributions), in another
order, so that seeds change which request comes when and what its tokens
are, not how much work a run holds.  Prompts are uniform random token
ids.  Paths are assigned in equal shares, in an order drawn from the
seed, and found again from the prompt's first tokens by ``Router``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

ROUTE_PREFIX = 8      # tokens of the prompt that the router reads


@dataclass
class Req:
    rid: int
    prompt: np.ndarray    # (plen,) int32
    max_new: int
    due: float            # seconds after the window opens
    path: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """The ``(i + 0.5) / n`` quantiles of a lognormal, rounded and
    clipped to ``[lo, hi]``."""
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def exponential_gaps(n: int, total: float) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps (the ``(i + 0.5) / n`` quantiles
    of an exponential) scaled to sum to ``total`` seconds."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return g * (total / g.sum())


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), stream])


def generate(mix: dict, *, n: int, seconds: float, seed: int, vocab: int,
             num_paths: int, open_loop: bool) -> list:
    """``n`` requests of ``mix``.  Open loop: arrivals spread over
    ``[0, seconds)`` by Poisson gaps.  Otherwise a backlog due at 0."""
    rng = rng_for(seed, 0)
    plen = rng.permutation(lognormal_quantiles(n, **mix["prompt"]))
    nout = rng.permutation(lognormal_quantiles(n, **mix["output"]))
    if open_loop:
        gaps = rng.permutation(exponential_gaps(n, seconds))
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    else:
        due = np.zeros(n)
    paths = rng.permutation(np.arange(n) % num_paths)
    toks = rng.integers(0, vocab, size=int(plen.sum()), dtype=np.int32)
    out, at = [], 0
    for i in range(n):
        out.append(Req(rid=i, prompt=toks[at:at + plen[i]],
                       max_new=int(nout[i]), due=float(due[i]),
                       path=int(paths[i])))
        at += int(plen[i])
    return out


class Router:
    """Maps a prompt to its path by its first ``ROUTE_PREFIX`` tokens
    (the engine's ``route_fn``)."""

    def __init__(self, reqs):
        self.table = {}
        for r in reqs:
            self.add(r.prompt, r.path)

    def add(self, prompt, path: int) -> None:
        self.table[np.asarray(prompt[:ROUTE_PREFIX], np.int32).tobytes()] = \
            int(path)

    def __call__(self, prompt) -> int:
        return self.table[np.asarray(prompt[:ROUTE_PREFIX],
                                     np.int32).tobytes()]
