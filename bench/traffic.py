"""Requests from a traffic file: a fixed pool of sizes, taken in an order
drawn from the seed.

A traffic file names its loop (a module under ``bench/loops/``) and the
distributions of prompt and output lengths. The pool holds ``pool``
requests whose lengths sit at evenly spaced quantiles of those
distributions, so every seed serves lengths from the same set; the seed
only shuffles the order and draws the prompt tokens. A small pool, taken
in turn, puts nearly the same set of sizes in flight for every seed, and so
gives every seed's window the same work.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile(dist: dict, q: float) -> int:
    """The ``q`` quantile of a length distribution, clipped to its range."""
    if dist["dist"] == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif dist["dist"] == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"] + 1)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(math.floor(x), dist["min"]), dist["max"]))


@dataclasses.dataclass(frozen=True)
class Request:
    index: int            # position in this run's order
    prompt: list
    max_new: int


class Pool:
    """The run's requests, in order: ``next()`` hands out the next one."""

    def __init__(self, traffic: dict, seed: int, vocab: int) -> None:
        self.sizes = sizes(traffic)
        self.order = np.random.default_rng([seed, 1]).permutation(
            len(self.sizes))
        self.seed = seed
        self.vocab = vocab
        self.taken = 0

    def next(self) -> Request:
        i = self.taken
        self.taken += 1
        p_len, max_new = self.sizes[self.order[i % len(self.order)]]
        rng = np.random.default_rng([self.seed, 2, i])
        prompt = rng.integers(0, self.vocab, p_len).tolist()
        return Request(i, prompt, max_new)


def sizes(traffic: dict) -> list[tuple[int, int]]:
    """The pool's (prompt, output) lengths: ``pool`` evenly spaced
    quantiles of each distribution, paired the same way for every seed."""
    n = traffic["pool"]
    qs = [(i + 0.5) / n for i in range(n)]
    prompts = [quantile(traffic["prompt_len"], q) for q in qs]
    outs = [quantile(traffic["output_len"], q) for q in qs]
    pairing = np.random.default_rng(0).permutation(n)
    return [(prompts[i], outs[j]) for i, j in enumerate(pairing)]


def prompt_lengths(traffic: dict) -> tuple[int, int]:
    """Shortest and longest prompt the mix sends."""
    prompts = [p for p, _ in sizes(traffic)]
    return min(prompts), max(prompts)
