"""Open loop: Poisson-shaped arrivals at ``rate`` requests per second, sent
on schedule whatever the server does. Each request is timed from when it
was due.

The gaps between arrivals are ``pool`` evenly spaced quantiles of the
exponential with mean 1/``rate`` (64 where the mix names no pool), scaled
so that their mean is exactly 1/``rate``. The seed only orders them, and
they are taken in turn. So every seed sends the same gaps: a window as long
as one turn of the pool gets ``pool`` requests (to one) whatever the seed,
with the local bursts of a Poisson run.
"""
import itertools
import math

POOL = 64


def gaps(spec: dict, rng) -> list[float]:
    """The pool's gaps in seconds, in the order the loop takes them."""
    n = spec.get("pool", POOL)
    quantiles = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / (spec["rate"] * sum(quantiles))
    return [scale * quantiles[i] for i in rng.permutation(n)]


def drive(ctx, spec: dict) -> None:
    order = gaps(spec, ctx.rng)
    due = ctx.clock()
    for i in itertools.count():
        due += order[i % len(order)]
        if ctx.stop.wait(max(0.0, due - ctx.clock())):
            return
        ctx.submit(ctx.pool.next(), due=due)
