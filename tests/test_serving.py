"""Serving-engine tests: continuous batching, ragged prompts, window-edge
prompts, sampling determinism, oracle equality, and KV-cache CPU offload
(mirror + swap/reload) under every reload policy.

The oracle is :func:`repro.serve.naive_generate` — an unbatched prefill +
single-row decode loop with the engine's (seed, rid, position) key
schedule. Every engine configuration (bucketing, padding, offload,
preemption, reload order) must reproduce it token-for-token."""
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import build_model
from repro.serve import (Engine, PagedKVCache, RELOAD_POLICY_NAMES,
                         ServeConfig, naive_generate)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(get_arch("olmo-1b"))
    model = build_model(cfg)
    return model, model.init(KEY)


def oracle(lm, prompts, *, max_new, max_len, seed=0, temperature=0.0):
    model, params = lm
    return [naive_generate(model, params, p, max_new=max_new,
                           max_len=max_len, rid=i, seed=seed,
                           temperature=temperature)
            for i, p in enumerate(prompts)]


# ------------------------------------------------------------------ basics
def test_ragged_batch_matches_oracle(lm):
    model, params = lm
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11], [12, 13, 14, 15, 16]]
    cfg = ServeConfig(max_len=64, batch_buckets=(1, 2, 4), block_size=16)
    out = Engine(model, params, cfg).generate(prompts, max_new=6)
    assert out == oracle(lm, prompts, max_new=6, max_len=64)


def test_padded_rows_inert(lm):
    """One request in a multi-slot bucket: padding slots must not perturb
    the live row (the old engine teacher-forced zeros into them forever)."""
    model, params = lm
    cfg = ServeConfig(max_len=64, batch_buckets=(4,), block_size=16)
    out = Engine(model, params, cfg).generate([[1, 2, 3]], max_new=5)
    solo = ServeConfig(max_len=64, batch_buckets=(1,), block_size=16)
    assert out == Engine(model, params, solo).generate([[1, 2, 3]],
                                                       max_new=5)
    assert out == oracle(lm, [[1, 2, 3]], max_new=5, max_len=64)


def test_prompt_exactly_fills_window(lm):
    """P == max_len crashed the old engine (None into np.where); now the
    first token samples from prefill logits and the request completes."""
    model, params = lm
    cfg = ServeConfig(max_len=32, batch_buckets=(1, 2), block_size=8)
    prompts = [list(range(1, 33)), [5, 6, 7]]
    out = Engine(model, params, cfg).generate(prompts, max_new=4)
    assert len(out[0]) == 1                     # window full after prefill
    assert len(out[1]) == 4
    assert out == oracle(lm, prompts, max_new=4, max_len=32)


def test_prompt_near_window_truncates(lm):
    model, params = lm
    cfg = ServeConfig(max_len=32, batch_buckets=(1,), block_size=8)
    out = Engine(model, params, cfg).generate([list(range(1, 31))],
                                              max_new=10)
    assert len(out[0]) == 3                     # 32 - 30 + 1
    assert out == oracle(lm, [list(range(1, 31))], max_new=10, max_len=32)


def test_queue_exceeds_largest_bucket(lm):
    """Continuous batching: 6 requests through 2 slots, admissions as slots
    free up."""
    model, params = lm
    prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(6)]
    cfg = ServeConfig(max_len=64, batch_buckets=(1, 2), block_size=16)
    eng = Engine(model, params, cfg)
    out = eng.generate(prompts, max_new=4)
    assert out == oracle(lm, prompts, max_new=4, max_len=64)
    assert eng.stats.tokens == 24
    for rid in range(len(prompts)):     # online hygiene: free finished reqs
        eng.release(rid)
    assert not eng.reqs and not eng._block_seq


def test_temperature_determinism_and_oracle(lm):
    model, params = lm
    prompts = [[1, 2, 3], [9, 8, 7, 6], [5]]
    cfg = ServeConfig(max_len=64, batch_buckets=(1, 2, 4), block_size=16,
                      temperature=0.7)
    a = Engine(model, params, cfg).generate(prompts, max_new=6, seed=11)
    b = Engine(model, params, cfg).generate(prompts, max_new=6, seed=11)
    assert a == b                               # fixed seed → reproducible
    assert a == oracle(lm, prompts, max_new=6, max_len=64, seed=11,
                       temperature=0.7)
    c = Engine(model, params, cfg).generate(prompts, max_new=6, seed=12)
    assert c != a                               # seed actually matters


def test_bad_requests_rejected(lm):
    model, params = lm
    eng = Engine(model, params, ServeConfig(max_len=32, block_size=8))
    with pytest.raises(ValueError):
        eng.submit([], 4)
    with pytest.raises(ValueError):
        eng.submit(list(range(40)), 4)
    with pytest.raises(ValueError):
        eng.submit([1, 2], 0)


def test_recurrent_families_rejected():
    cfg = reduced(get_arch("rwkv6-7b"))
    model = build_model(cfg)
    with pytest.raises(ValueError):
        Engine(model, {}, ServeConfig())


# ----------------------------------------------------------------- offload
def test_offload_smoke_two_requests(lm):
    """Fast-lane serving smoke: tiny model, 2 requests, offload forced on
    (every block cold), with preemption forcing a real swap/reload cycle.
    Outputs must match the no-offload oracle and traffic must be real."""
    model, params = lm
    prompts = [list(range(1, 25)), list(range(30, 48))]
    cfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                      offload=True, hot_window=0, offload_fraction=1.0,
                      preempt_every=3, h2d_bw=500e6, d2h_bw=500e6)
    eng = Engine(model, params, cfg)
    out = eng.generate(prompts, max_new=8)
    assert out == oracle(lm, prompts, max_new=8, max_len=64)
    st = eng.stats
    assert st.offload_bytes > 0 and st.reload_bytes > 0
    assert st.offloaded_fraction >= 0.5
    assert st.swaps >= 1
    # everything freed once requests finish
    assert eng.host.resident_bytes == 0


@pytest.mark.parametrize("policy", RELOAD_POLICY_NAMES)
def test_reload_policy_order_independence(lm, policy):
    """The TURNIP property, serving edition: reload order changes timing,
    never results."""
    model, params = lm
    prompts = [list(range(1, 20)), list(range(5, 33)), [7, 8, 9, 10]]
    cfg = ServeConfig(max_len=64, batch_buckets=(1, 2), block_size=8,
                      offload=True, hot_window=8, preempt_every=2,
                      reload_policy=policy, h2d_bw=300e6, d2h_bw=300e6)
    out = Engine(model, params, cfg).generate(prompts, max_new=6)
    assert out == oracle(lm, prompts, max_new=6, max_len=64)


def test_mirrored_cold_blocks_survive_double_preempt(lm):
    """A request preempted twice must restore bit-identical state both
    times (stale-tail-block invalidation is the regression target)."""
    model, params = lm
    prompts = [list(range(1, 30)), list(range(2, 28)), list(range(3, 31))]
    cfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                      offload=True, hot_window=0, preempt_every=2,
                      h2d_bw=500e6, d2h_bw=500e6)
    eng = Engine(model, params, cfg)
    out = eng.generate(prompts, max_new=8)
    assert out == oracle(lm, prompts, max_new=8, max_len=64)
    assert eng.stats.swaps >= 6                  # every request swapped twice


def test_stale_transfer_after_release_is_safe(lm):
    """A transfer completing after its request was released must be a
    no-op on the DMA thread, not a KeyError that silently kills the
    stream and wedges the engine."""
    from repro.serve.engine import _Transfer, get_reload_policy
    from repro.core.dispatch import D2H
    model, params = lm
    eng = Engine(model, params, ServeConfig(max_len=32, block_size=8))
    rid = eng.submit([1, 2, 3], 2)
    eng.run()
    eng.release(rid)
    stale = _Transfer(D2H, rid, 0, seq=0, nbytes=64)
    eng._service_d2h(stale)                      # must not raise
    pol = get_reload_policy("critical-path")
    pol.prepare(eng)
    assert pol.priority(stale) < 0               # drains stale items first


def test_a_decode_step_releases_the_cache_it_replaced(lm, monkeypatch):
    """Every cache update replaces the whole cache; the one a decode step
    replaced must be gone before the next iteration's paging and prefill
    make more, or the device holds two caches between steps."""
    model, params = lm
    cfg = ServeConfig(max_len=48, batch_buckets=(2,), block_size=16)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [8, 9]]
    live = []
    prefill = Engine._prefill_admit

    def counting_prefill(self, admits):
        shapes = [leaf.shape for leaf in self.kv.cache.values()]
        live.append(sum(a.shape in shapes for a in jax.live_arrays()))
        return prefill(self, admits)
    monkeypatch.setattr(Engine, "_prefill_admit", counting_prefill)
    eng = Engine(model, params, cfg)
    assert eng.generate(prompts, max_new=4) == oracle(lm, prompts,
                                                      max_new=4, max_len=48)
    # the third prompt is admitted after decode steps have run
    assert len(live) == 2 and eng.stats.decode_steps > 0
    assert live == [len(eng.kv.cache)] * 2, live


# -------------------------------------------------------------- disk tier
@pytest.mark.parametrize("policy", RELOAD_POLICY_NAMES)
def test_tiered_kv_matches_oracle_every_policy(lm, policy):
    """Tier transparency, serving edition: a bounded host KV mirror with
    disk spill (two-hop reloads on the dedicated disk stream) reproduces
    the unbounded oracle token-for-token under every reload policy."""
    model, params = lm
    prompts = [list(range(1, 25)), list(range(30, 48)), [7, 8, 9, 10, 11]]
    cfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                      offload=True, hot_window=0, offload_fraction=1.0,
                      preempt_every=3, reload_policy=policy,
                      h2d_bw=500e6, d2h_bw=500e6,
                      host_kv_bytes=1, disk_bw=300e6)  # everything spills
    with Engine(model, params, cfg) as eng:
        out = eng.generate(prompts, max_new=8)
        assert out == oracle(lm, prompts, max_new=8, max_len=64)
        st = eng.stats
        assert st.disk_spill_bytes > 0 and st.disk_load_bytes > 0
        assert st.swaps >= 1
        # hierarchy fully drained once every request finished
        assert eng.host.resident_bytes == 0
        assert eng.host.disk.resident_bytes == 0


def test_swapped_queue_prefetch_fires_and_stays_oracle_exact(lm):
    """NEO-style predictive prefetch (DESIGN.md §11): with a host budget
    wide enough to hold a couple of blocks, the engine stages the
    next-scheduled swapped request's disk-resident blocks back to host
    *before* admission (prefetch_bytes > 0) — and tokens are identical to
    the oracle and to a prefetch-off run (timing only, never results)."""
    model, params = lm
    prompts = [list(range(1, 25)), list(range(30, 48)), [7, 8, 9, 10, 11]]
    want = oracle(lm, prompts, max_new=8, max_len=64)
    # a ~3-block host budget: swapped-out requests' mirrors spill to disk,
    # yet the prefetcher keeps headroom (net of in-flight reload
    # reservations) to stage the next resume back in
    blk = PagedKVCache(model, 1, 64, block_size=8).block_nbytes

    def run(prefetch):
        cfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                          offload=True, hot_window=0, offload_fraction=1.0,
                          preempt_every=3, h2d_bw=500e6, d2h_bw=500e6,
                          disk_bw=300e6, host_kv_bytes=3 * blk,
                          prefetch_swapped=prefetch)
        with Engine(model, params, cfg) as eng:
            out = eng.generate(prompts, max_new=8)
            return out, eng.stats

    out_on, st_on = run(True)
    out_off, st_off = run(False)
    assert out_on == want and out_off == want
    assert st_on.disk_spill_bytes > 0            # the disk tier was real
    assert st_on.prefetch_bytes > 0              # prediction actually fired
    assert st_off.prefetch_bytes == 0


def test_tiered_kv_roomy_host_never_touches_disk(lm):
    """A host tier wider than the KV working set must behave exactly like
    the plain HostStore path: zero disk traffic."""
    model, params = lm
    prompts = [list(range(1, 20)), [4, 5, 6]]
    cfg = ServeConfig(max_len=64, batch_buckets=(1, 2), block_size=8,
                      offload=True, hot_window=0, preempt_every=2,
                      h2d_bw=500e6, d2h_bw=500e6,
                      host_kv_bytes=1 << 30)
    with Engine(model, params, cfg) as eng:
        out = eng.generate(prompts, max_new=6)
        assert out == oracle(lm, prompts, max_new=6, max_len=64)
        assert eng.stats.disk_spill_bytes == 0
        assert eng.stats.disk_load_bytes == 0


def test_disk_log_compacts_on_the_disk_stream(lm, monkeypatch):
    """The engine's disk tier never compacts inside a drop: drops happen
    under the engine lock, and a rewrite of the live tier takes seconds at
    full size. The disk stream compacts it, off the lock, and tokens stay
    the oracle's."""
    from repro.core.stores import DiskStore
    model, params = lm
    prompts = [list(range(1, 25)), list(range(30, 48)), [7, 8, 9, 10, 11]]
    cfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                      offload=True, hot_window=0, preempt_every=3,
                      h2d_bw=500e6, d2h_bw=500e6,
                      host_kv_bytes=1, disk_bw=300e6)
    threads = []
    compact = DiskStore._compact

    def recording_compact(self, live):
        threads.append(threading.current_thread().name)
        return compact(self, live)
    monkeypatch.setattr(DiskStore, "_compact", recording_compact)
    with Engine(model, params, cfg) as eng:
        assert not eng.host.disk.compact_inline
        eng.host.disk.compact_min_bytes = 1
        out = eng.generate(prompts, max_new=8)
        assert out == oracle(lm, prompts, max_new=8, max_len=64)
        assert eng.host.disk.n_compactions > 0
        assert eng.stats.disk_compact_time > 0
    assert threads and set(threads) == {"serve-dma-disk"}


# ------------------------------------------------------------ shared pool
@pytest.mark.parametrize("arb", ("static", "demand", "priority"))
def test_pooled_engine_matches_oracle_and_bounds_pool(lm, arb):
    """Shared-pool lane (DESIGN.md §12): the engine's KV mirror living in
    an arbitrated HostPool — reservations gate every host-bound transfer —
    must stay token-exact vs the oracle under every arbitration policy,
    with combined occupancy never past the pool budget and every lease
    drained once the queue empties."""
    from repro.core import HostPool
    model, params = lm
    prompts = [list(range(1, 25)), list(range(30, 48)), [7, 8, 9, 10, 11]]
    want = oracle(lm, prompts, max_new=8, max_len=64)
    blk = PagedKVCache(model, 1, 64, block_size=8).block_nbytes
    # priority pool is deliberately tight (revocations + deferrals fire);
    # static must cover the largest resume set out of its fixed kv share
    pool = HostPool((6 if arb == "priority" else 8) * blk, policy=arb)
    cfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                      offload=True, hot_window=0, offload_fraction=1.0,
                      preempt_every=3, h2d_bw=500e6, d2h_bw=500e6,
                      disk_bw=300e6)
    with Engine(model, params, cfg, pool=pool) as eng:
        out = eng.generate(prompts, max_new=8)
        assert out == want
        snap = pool.snapshot()
        assert snap["peak_bytes"] > 0
        assert snap["peak_bytes"] <= snap["capacity"]
        assert eng.host.resident_bytes == 0
        assert eng.host.disk.resident_bytes == 0
        for name in ("kv", "prefetch"):
            assert snap["leases"][name]["used"] == 0
        if arb == "priority":
            assert eng.stats.disk_spill_bytes > 0    # tier really pressed
            assert eng.stats.lease_deferrals > 0


def test_pool_drains_when_run_ends_with_mirrors_queued(lm):
    """Eager mirrors still queued on the d2h stream when the last request
    finishes are abandoned at stream shutdown. Their reservations must go
    back to the pool, or the kv lease stays charged after the run."""
    from repro.core import HostPool
    model, params = lm
    prompt = list(range(1, 25))
    blk = PagedKVCache(model, 1, 64, block_size=8).block_nbytes
    pool = HostPool(16 * blk)
    cfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                      offload=True, hot_window=0, offload_fraction=1.0,
                      d2h_bw=blk / 0.2)          # 0.2 s per mirrored block
    with Engine(model, params, cfg, pool=pool) as eng:
        out = eng.generate([prompt], max_new=4)
        assert out == oracle(lm, [prompt], max_new=4, max_len=64)
        # three cold blocks were queued; the run ended before all landed
        assert eng.stats.offload_bytes < 3 * blk
        assert pool.snapshot()["leases"]["kv"]["used"] == 0


def test_runtime_and_serving_share_one_arbitrated_pool(lm):
    """The headline scenario: a MEMGRAPH plan's offload traffic and the
    serving engine's KV mirror running *concurrently* against ONE
    HostPool. Both consumers' outputs must be byte-identical to isolated
    runs, and the pool bound must hold throughout."""
    import threading
    from repro.core import BuildConfig, HostPool, build_memgraph
    from repro.core.runtime import TurnipRuntime, eval_taskgraph
    from helpers import fig3_taskgraph, int_inputs
    model, params = lm
    tg = fig3_taskgraph()
    inputs = int_inputs(tg)
    ref = eval_taskgraph(tg, inputs)
    res = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                         size_fn=lambda v: 1))
    assert res.n_spills > 0
    # isolated baselines: runtime on a private store, engine on its own
    rr_iso = TurnipRuntime(tg, res, mode="nondet", policy="random",
                           seed=3).run(inputs)
    prompts = [list(range(1, 25)), list(range(30, 48)), [7, 8, 9]]
    want = oracle(lm, prompts, max_new=6, max_len=64)
    blk = PagedKVCache(model, 1, 64, block_size=8).block_nbytes
    scfg = ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                       offload=True, hot_window=0, offload_fraction=1.0,
                       preempt_every=3, h2d_bw=500e6, d2h_bw=500e6,
                       disk_bw=300e6)

    pool = HostPool(8 * blk + 2 * rr_iso.peak_host_bytes + 1,
                    policy="priority")
    mem_lease = pool.lease("memgraph", min_bytes=rr_iso.peak_host_bytes,
                           priority=1)
    rt_out: dict = {}

    def run_runtime():
        rt = TurnipRuntime(tg, res, mode="nondet", policy="random",
                           seed=3, host_lease=mem_lease)
        rt_out["rr"] = rt.run(inputs)

    with Engine(model, params, scfg, pool=pool) as eng:
        t = threading.Thread(target=run_runtime)
        t.start()
        out = eng.generate(prompts, max_new=6)
        t.join(60)
        assert not t.is_alive(), "pooled runtime wedged"
    assert out == want                          # serving: oracle-exact
    rr = rt_out["rr"]
    for k in ref:                               # runtime: oracle-exact
        np.testing.assert_array_equal(rr.outputs[k], ref[k])
    snap = pool.snapshot()
    assert snap["peak_bytes"] > 0
    assert snap["peak_bytes"] <= snap["capacity"]
    assert snap["leases"]["memgraph"]["peak"] <= mem_lease.min_bytes
    assert snap["used_bytes"] == 0              # everything drained


# ------------------------------------------------------------ paged cache
def test_paged_cache_block_roundtrip(lm):
    model, _ = lm
    kv = PagedKVCache(model, 2, 32, block_size=8)
    assert kv.n_blocks == 4
    assert kv.n_token_blocks(0) == 0 and kv.n_token_blocks(9) == 2
    leaf = kv.cache["k"]
    kv.cache["k"] = leaf.at[:, 1, 8:16].set(1.5)
    data = kv.read_block(1, 1)
    assert float(np.asarray(data["k"]).mean()) == 1.5
    assert sum(d.nbytes for d in data.values()) == kv.block_nbytes
    kv.drop_slot(1)
    assert float(np.abs(np.asarray(kv.cache["k"][:, 1])).max()) == 0.0
    kv.write_block(1, 1, data)
    assert float(np.asarray(kv.cache["k"][:, 1, 8:16]).mean()) == 1.5
    kv.grow(4)
    assert kv.cache["k"].shape[1] == 4
    assert float(np.asarray(kv.cache["k"][:, 1, 8:16]).mean()) == 1.5


def test_paged_cache_rejects_recurrent_cache():
    cfg = reduced(get_arch("rwkv6-7b"))
    model = build_model(cfg)
    with pytest.raises(ValueError):
        PagedKVCache(model, 2, 32, block_size=8)


def test_host_store_block_hooks():
    from repro.core.runtime import HostStore
    hs = HostStore({})
    blk = {"k": np.ones((2, 8), np.float32), "v": np.ones((2, 8), np.float32)}
    hs.put_offload(("r0", 0), blk)
    assert hs.offload_bytes == 128 and hs.resident_bytes == 128
    got = hs.get_offload(("r0", 0))
    assert hs.reload_bytes == 128
    np.testing.assert_array_equal(got["k"], blk["k"])
    hs.pop_offload(("r0", 0))
    assert hs.resident_bytes == 0
    hs.pop_offload(("r0", 0))                    # idempotent


def test_bytearena_drop_invalidates():
    """Audit fix: ByteArena.drop was a silent no-op — dropped extents must
    now raise RaceError on read, matching SlotTable's contract."""
    from repro.core.memgraph import Loc, RaceError
    from repro.core.runtime import ByteArena
    arena = ByteArena({0: 64})
    loc = Loc(device=0, offset=0, size=16)
    arena.write(loc, np.arange(4, dtype=np.float32))
    np.testing.assert_array_equal(arena.read(loc),
                                  np.arange(4, dtype=np.float32))
    arena.drop(loc)
    with pytest.raises(RaceError):
        arena.read(loc)


# ------------------------------------------------------------ chip smoke
def _chip_smoke():
    """``chip_smoke.py`` lives at the repository root, outside the package."""
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_serving_phase_on_cpu():
    """The chip smoke's serving phase at the reduced Qwen2.5 config: every
    tier carries traffic, and in float32 on the CPU every request matches
    the oracle exactly (no token departs, every logit row within bound)."""
    smoke = _chip_smoke()
    r = smoke.serve_phase(reduced(get_arch("qwen2.5-3b")), seed=0)
    assert r["failures"] == []
    for key in ("swaps", "offload_bytes", "reload_bytes", "disk_load_bytes"):
        assert r[key] > 0, key
    assert r["completed"] == r["oracle_matched"] == "12/12"
    assert r["positions_checked"] == 12 * smoke.MAX_NEW
    assert r["token_departures"] == []


def test_chip_smoke_oracle_comparison_allows_only_near_ties():
    """Every position is compared with the oracle teacher-forced on the
    engine's tokens: a token departure passes only while the engine's
    whole logit row stays within ``TOL`` of the row's largest logit, and
    an earlier departure excuses nothing after it."""
    smoke = _chip_smoke()
    tol = 4.0 * smoke.TOL                    # absolute, at a row max of 4
    o1 = np.zeros(8, np.float32)
    o1[3], o1[5] = 4.0, 4.0 - tol / 2        # a near-tie
    o2 = np.zeros(8, np.float32)
    o2[1] = 2.0
    oracle = [([3, 1], [o1, o2])]
    r = smoke.compare_with_oracle([[3, 1]], {0: [o1, o2]}, oracle)
    assert (r["matched"], r["departures"], r["failures"]) == (1, [], [])
    flip = o1.copy()
    flip[5] = 4.0 + tol / 4                  # the tie flips, within bound
    r = smoke.compare_with_oracle([[5, 1]], {0: [flip, o2]}, oracle)
    assert (r["matched"], r["failures"]) == (0, [])
    assert [d["pos"] for d in r["departures"]] == [0]
    far = o2.copy()
    far[6] = 2.5                             # beyond bound after the tie
    r = smoke.compare_with_oracle([[5, 6]], {0: [flip, far]}, oracle)
    assert len(r["failures"]) == 1 and "pos 1" in r["failures"][0]
    r = smoke.compare_with_oracle([[3]], {0: [o1]}, oracle)   # too short
    assert r["failures"]
