"""The h2d stream stages a resumed request's blocks on the device.

``_service_h2d`` copies each reloaded block onto the cache's device off the
run loop, up to ``PagedKVCache.staging_cap`` bytes not yet applied; a block
past the cap reaches ``restore_slot`` as host arrays and is copied there.
Either way the tokens are the unbatched oracle's."""
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import build_model
from repro.serve import (Engine, PagedKVCache, RELOAD_POLICY_NAMES,
                         ServeConfig, naive_generate)

PROMPTS = [list(range(1, 25)), list(range(30, 48)), [7, 8, 9, 10, 11]]


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(get_arch("olmo-1b"))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def want(lm):
    model, params = lm
    return [naive_generate(model, params, p, max_new=8, max_len=64, rid=i)
            for i, p in enumerate(PROMPTS)]


def tiered(policy: str) -> ServeConfig:
    """One slot, preemption every 3 steps, every mirrored block spilled to
    disk: each resume reloads disk -> host -> device."""
    return ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                       offload=True, hot_window=0, preempt_every=3,
                       reload_policy=policy, h2d_bw=500e6, d2h_bw=500e6,
                       host_kv_bytes=1, disk_bw=300e6)


def record_puts(monkeypatch, on_put) -> None:
    """Call ``on_put(kv, x)`` before every ``PagedKVCache.put``."""
    put = PagedKVCache.put

    def recording_put(kv, x):
        on_put(kv, x)
        return put(kv, x)
    monkeypatch.setattr(PagedKVCache, "put", recording_put)


def block_shaped(kv, x) -> bool:
    return tuple(np.shape(x)) in {shape for shape, _ in
                                  kv.leaf_spec().values()}


@pytest.mark.parametrize("policy", RELOAD_POLICY_NAMES)
def test_resumed_blocks_are_copied_on_the_h2d_stream(lm, want, policy,
                                                     monkeypatch):
    """Every reloaded block is staged by the h2d stream: the run loop never
    copies a block's host arrays, and staged bytes stay within the cap and
    are all released by the end of the run."""
    loop = threading.current_thread()
    puts = []
    with Engine(*lm, tiered(policy)) as eng:
        def on_put(kv, x):
            if block_shaped(kv, x):
                puts.append((threading.current_thread(),
                             isinstance(x, jax.Array),
                             len(eng._staged) * kv.block_nbytes
                             <= kv.staging_cap))
        record_puts(monkeypatch, on_put)
        assert eng.generate(PROMPTS, max_new=8) == want
    st = eng.stats
    assert st.h2d_staged_blocks > 0 and st.h2d_unstaged_blocks == 0
    assert st.h2d_copy_bytes == st.reload_bytes > 0
    on_loop = [on_device for thread, on_device, _ in puts if thread is loop]
    on_h2d = [on_device for thread, on_device, _ in puts
              if thread.name == "serve-dma-h2d"]
    assert on_loop and all(on_loop)
    assert len(on_h2d) == st.h2d_staged_blocks * len(eng.kv.cache)
    assert not any(on_h2d)
    assert all(within for _, _, within in puts)
    assert not eng._staged


@pytest.mark.parametrize("policy", RELOAD_POLICY_NAMES)
def test_a_zero_staging_cap_copies_every_block_on_the_loop(
        lm, want, policy, monkeypatch):
    monkeypatch.setattr(PagedKVCache, "staging_cap", 0)
    with Engine(*lm, tiered(policy)) as eng:
        assert eng.generate(PROMPTS, max_new=8) == want
    st = eng.stats
    assert st.h2d_unstaged_blocks > 0 and st.h2d_staged_blocks == 0
    assert st.h2d_copy_bytes == 0 and st.h2d_copy_time == 0


def test_a_one_block_cap_mixes_staged_and_host_blocks(lm, want, monkeypatch):
    """With room for one staged block, a resume's other blocks fall back to
    the loop-side copy, and one restore_slot takes both kinds."""
    nbytes = PagedKVCache(lm[0], 1, 64, block_size=8).block_nbytes
    monkeypatch.setattr(PagedKVCache, "staging_cap", nbytes)
    staged = []
    with Engine(*lm, tiered("critical-path")) as eng:
        record_puts(monkeypatch, lambda kv, x: staged.append(len(eng._staged)))
        assert eng.generate(PROMPTS, max_new=8) == want
    st = eng.stats
    assert st.h2d_staged_blocks > 0 and st.h2d_unstaged_blocks > 0
    assert max(staged) == 1


def test_restore_slot_takes_host_and_device_blocks_alike(lm):
    """The same blocks as host arrays, as device arrays, or mixed leave a
    bit-identical cache."""
    model, _ = lm
    rng = np.random.default_rng(0)
    shapes = PagedKVCache(model, 2, 32, block_size=8).cache
    values = {k: rng.standard_normal(leaf.shape).astype(leaf.dtype)
              for k, leaf in shapes.items()}
    caches = []
    for kind in ("host", "device", "mixed"):
        kv = PagedKVCache(model, 2, 32, block_size=8)
        kv.cache = {k: kv.put(v) for k, v in values.items()}
        blocks = [kv.read_block(0, b) for b in range(3)]
        if kind != "host":
            blocks = [{k: kv.put(v) for k, v in b.items()}
                      if kind == "device" or i % 2 else b
                      for i, b in enumerate(blocks)]
        kv.restore_slot(1, blocks)
        caches.append({k: np.asarray(v) for k, v in kv.cache.items()})
    host = caches[0]
    for k, leaf in host.items():
        np.testing.assert_array_equal(leaf[:, 1, :24], leaf[:, 0, :24])
        for other in caches[1:]:
            assert np.array_equal(leaf, other[k]), k
