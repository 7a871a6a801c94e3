"""Traffic: the pool of sizes is fixed, the seed orders it and draws the
tokens, and the loops send what their files say."""
import collections
import queue
import threading

import numpy as np
import pytest

from bench_tiny import ROOT
from bench import harness
from bench.traffic import Pool, prompt_lengths, quantile

MIXES = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    traffic = harness.load_json(ROOT / f"bench/traffic/{mix}.json")
    a, b = Pool(traffic, 2**31 + 99, 1000), Pool(traffic, 2**31 + 99, 1000)
    for _ in range(20):
        ra, rb = a.next(), b.next()
        assert (ra.prompt, ra.max_new) == (rb.prompt, rb.max_new)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_sizes_in_another_order(mix):
    traffic = harness.load_json(ROOT / f"bench/traffic/{mix}.json")
    n = traffic["pool"]
    a, b = Pool(traffic, 1, 1000), Pool(traffic, 2, 1000)
    sa = [(len(r.prompt), r.max_new) for r in (a.next() for _ in range(n))]
    sb = [(len(r.prompt), r.max_new) for r in (b.next() for _ in range(n))]
    assert sa != sb
    assert collections.Counter(sa) == collections.Counter(sb)
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    assert all(lo <= p <= hi for p, _ in sa)


@pytest.mark.parametrize("mix", MIXES)
def test_warm_lengths_are_the_pools(mix):
    traffic = harness.load_json(ROOT / f"bench/traffic/{mix}.json")
    pool = Pool(traffic, 7, 1000)
    prompts = [p for p, _ in pool.sizes]
    assert prompt_lengths(traffic) == (min(prompts), max(prompts))
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    assert lo <= min(prompts) <= max(prompts) <= hi


def test_quantiles_follow_the_distribution():
    d = dict(dist="lognormal", median=1024, sigma=0.5, min=256, max=1792)
    assert quantile(d, 0.5) == 1024
    assert quantile(d, 0.001) == 256 and quantile(d, 0.999) == 1792
    u = dict(dist="uniform", min=64, max=256)
    assert quantile(u, 0.0) == 64 and quantile(u, 0.9999) == 256


class _FakeDriver:
    """A loop's view of the harness, with a server that answers at once."""

    def __init__(self, pool):
        self.pool = pool
        self.completions = queue.SimpleQueue()
        self.stop = threading.Event()
        self.rng = np.random.default_rng(0)
        self.clock = __import__("time").monotonic
        self.sent = []

    def submit(self, req, due=None):
        self.sent.append((req, due))
        self.completions.put(len(self.sent))
        if len(self.sent) >= 40:
            self.stop.set()


@pytest.mark.parametrize("spec", [
    dict(loop="closed", clients=4),
    dict(loop="open", rate=2000.0),
])
def test_loops_send_until_stopped(spec):
    traffic = harness.load_json(ROOT / "bench/traffic/swap-long.json")
    drv = _FakeDriver(Pool(traffic, 5, 100))
    t = threading.Thread(target=harness.module("loops", spec["loop"]).drive,
                         args=(drv, spec))
    t.start()
    t.join(timeout=20)
    assert not t.is_alive()
    assert len(drv.sent) >= 40
    idx = [r.index for r, _ in drv.sent]
    assert idx == list(range(len(idx)))
    if spec["loop"] != "closed":
        dues = [d for _, d in drv.sent]
        assert dues == sorted(dues)
