"""The chat cell's parts: the open loop with pooled arrivals, the readers of
time to first token, the median gap, queue wait and the prefill's shares
of the chip, the knee sweep's window reading, and a tiny Qwen-shaped cell
served end to end under that loop, which the reference passes and the
float8 control fails.
"""
import dataclasses
import statistics

import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import ROOT, run_tiny, tiny_cell
from bench import harness, knee
from bench.models import dense
from repro.models import LM
from repro.serve import engine as engine_mod

OPEN = harness.module("loops", "open")
CHAT = harness.load_json(ROOT / "bench/traffic/chat-open.json")


class _VirtualDriver:
    """A loop's view of the harness on a virtual clock: waiting advances
    the clock, and the stop flag rises at ``end`` seconds."""

    def __init__(self, seed, end):
        self.rng = np.random.default_rng([seed, 3])
        self.now, self.end = 0.0, end
        self.stop = self
        self.dues = []
        self.pool = self

    def clock(self):
        return self.now

    def wait(self, dt):
        self.now += dt
        return self.now >= self.end

    def next(self):
        return len(self.dues)

    def submit(self, req, due=None):
        assert req == len(self.dues)
        self.dues.append(due)


def _dues(spec, seed, end):
    drv = _VirtualDriver(seed, end)
    OPEN.drive(drv, spec)
    return drv.dues


def test_pooled_gaps_have_mean_one_over_rate_and_the_same_set():
    spec = dict(rate=2.5, pool=64)
    a = OPEN.gaps(spec, np.random.default_rng(1))
    b = OPEN.gaps(spec, np.random.default_rng(2))
    assert len(a) == 64 and a != b and sorted(a) == sorted(b)
    assert statistics.fmean(a) == pytest.approx(1 / 2.5, rel=1e-12)
    # evenly spaced quantiles of an exponential: the median gap is its
    # median, ln 2 times the mean, to within the midpoint rule's rescale
    assert statistics.median(a) == pytest.approx(np.log(2) / 2.5, rel=0.02)


@pytest.mark.parametrize("spec", [dict(rate=2.5, pool=64),
                                  dict(rate=CHAT["rate"], pool=CHAT["pool"])])
def test_a_window_gets_the_same_number_of_requests_on_every_seed(spec):
    """Three seeds, each window as long as one turn of the pool and
    starting at another point of the run: the same count to one."""
    turn = spec["pool"] / spec["rate"]
    counts = []
    for seed, start in ((2**31 + 11, 7.3), (5, 13.9), (2**33 + 1, 21.2)):
        dues = _dues(spec, seed, start + 2 * turn)
        counts.append(sum(start <= d < start + turn for d in dues))
    assert max(counts) - min(counts) <= 1
    assert all(abs(c - spec["pool"]) <= 1 for c in counts)


def test_the_loop_sends_in_order():
    dues = _dues(dict(rate=4.0, pool=16), 7, 10.0)
    assert dues == sorted(dues) and len(dues) == pytest.approx(40, abs=1)


def test_a_mix_without_a_pool_takes_the_default_pool_of_gaps():
    a = OPEN.gaps(dict(rate=2000.0), np.random.default_rng(3))
    assert len(a) == OPEN.POOL
    assert statistics.fmean(a) == pytest.approx(1 / 2000.0, rel=1e-12)


def _run(sent, tokens, stats0=None, stats1=None, prefill=(), trace=None,
         arch=None, peaks=None):
    run = harness.Run(arch=arch or {}, work=dense, peaks=peaks or {},
                      t0=10.0, t1=20.0, setup_s=1.0, sent=sent,
                      tokens=tokens, done={}, decode=[],
                      prefill=list(prefill), stats0=stats0 or {},
                      stats1=stats1 or {}, compiles=[12.0, 25.0])
    run.trace = trace
    return run


def _read(name, run):
    return harness.module("metrics", name).read(run)


def test_ttft_is_due_to_first_token_and_a_waiting_request_counts():
    # ten requests due in the window, one before it, one after it
    sent = {r: (None, 10.0 + r, 10.0 + r) for r in range(10)}
    sent[98] = (None, 9.0, 9.0)
    sent[99] = (None, 20.5, 20.5)
    tokens = {r: [10.0 + r + 0.1 * (r + 1), 30.0] for r in range(8)}
    tokens[98] = [9.01]
    # requests 8 and 9, due at 18.0 and 19.0, have no token by the close
    # at 20.0: they wait 2.0 and 1.0 so far, and the 80th percentile lies
    # between the longest answered wait and the shorter of those two
    waits = [0.1 * (r + 1) for r in range(8)] + [2.0, 1.0]
    run = _run(sent, tokens, {"swaps": 3}, {"swaps": 5})
    assert _read("ttft_p80_s", run) == pytest.approx(0.8 + 0.2 * (1.0 - 0.8))
    assert _read("ttft_p80_s", run) == pytest.approx(
        float(np.percentile(waits, 80)))
    # tokens after the close count only up to the close
    tokens[8], tokens[9] = [24.0], [24.5]
    assert _read("ttft_p80_s", run) == pytest.approx(0.84)
    load = knee.window_load(run)
    assert load["due"] == 10 and load["no_first_token_at_close"] == 2
    # nothing finished: every request due in the window is unfinished
    assert load["unfinished_at_close"] == 10
    assert load["ttft_p50_s"] == pytest.approx(float(np.median(waits)))
    assert load["compiles_in_window"] == 1 and load["swaps"] == 2
    assert _read("ttft_p80_s", _run({99: sent[99]}, {})) is None


def test_itl_p50_is_the_median_of_every_gap_of_the_window():
    """Gaps of two requests, one of them still running at the close, and
    tokens before the window that open no gap inside it."""
    tokens = {1: [9.5, 10.5, 10.6, 10.8], 2: [12.0, 12.4, 19.0]}
    run = _run({}, tokens)
    run.done = {1: 10.8}
    # request 1: 0.1, 0.2; request 2: 0.4, 6.6 and 1.0 open at the close
    gaps = [0.1, 0.2, 0.4, 6.6, 1.0]
    assert run.token_gaps() == pytest.approx(gaps)
    assert _read("itl_p50_ms", run) == pytest.approx(1e3 * 0.4)
    assert _read("itl_p99_ms", run) == pytest.approx(
        1e3 * float(np.percentile(gaps, 99)))
    assert _read("itl_p50_ms", _run({}, {})) is None


def test_queue_ms_by_hand():
    stats0 = {"queue_time": 4.0, "admissions": 10}
    stats1 = {"queue_time": 5.5, "admissions": 16}
    assert _read("queue_ms", _run({}, {}, stats0, stats1)) == \
        pytest.approx(1e3 * 1.5 / 6)
    assert _read("queue_ms", _run({}, {}, stats0, dict(stats0))) is None
    assert _read("queue_ms", _run({}, {}, {}, {})) is None


class _Trace:
    def __init__(self, times):
        self._times = times

    def program_time(self):
        return self._times


def test_prefill_shares_of_the_chip_by_hand():
    """Two prompts of 300 and 500 tokens prefilled together, one of 1000
    alone, in 0.5 s of prefill program on the device and 0.8 s of the
    engine's prefill calls."""
    arch = dict(family="dense", n_layers=36, d_model=2048, n_heads=16,
                n_kv_heads=2, d_ff=11008, vocab_size=151936, norm="rmsnorm",
                norm_eps=1e-6, mlp="swiglu", qkv_bias=True, rope_theta=1e6,
                tie_embeddings=True, dtype="bfloat16")
    peaks = harness.load_peaks("TPU v5 lite")
    prefill = [(11.0, "r0", 3, 300), (11.0, "r0", 3, 500),
               (12.0, "r0", 9, 1000), (21.0, "r0", 12, 700)]
    run = _run({}, {}, {"prefill_time": 1.0}, {"prefill_time": 1.8},
               prefill=prefill, arch=arch, peaks=peaks,
               trace=_Trace({"jit_prefill": (0.5, 2),
                             "jit_decode_step": (3.0, 90)}))
    # per token: 36 layers of q, o (2048 x 2048), k, v (2048 x 256) and
    # the SwiGLU (3 x 2048 x 11008); the last position's logits; causal
    # attention over the real lengths
    per_tok = 36 * (2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008)

    def flops(lens):
        return (2 * sum(lens) * per_tok + 2 * len(lens) * 2048 * 151936
                + 2 * 36 * 16 * 128 * sum(n * (n + 1) for n in lens))

    total = flops([300, 500]) + flops([1000])
    least = 0.0
    for lens in ([300, 500], [1000]):
        least += max(flops(lens) / 197e12,
                     dense.prefill_work(arch, lens)[1] / 819e9)
    assert dense.prefill_work(arch, [300, 500])[0] == flops([300, 500])
    assert _read("prefill_roofline", run) == pytest.approx(100 * least / 0.5)
    assert _read("mfu.prefill", run) == pytest.approx(
        100 * total / (0.8 * 197e12))
    # no trace, or no prefill in the window: nothing to read
    assert _read("prefill_roofline", _run({}, {})) is None
    run.prefill = prefill[3:]
    assert _read("mfu.prefill", run) is None


# a Qwen-shaped tiny model: grouped-query attention with four query heads
# to each KV head, QKV biases, RMSNorm gains and tied embeddings
QWEN_TINY = dict(arch__n_heads=8, arch__n_kv_heads=2, arch__qkv_bias=True,
                 arch__norm="rmsnorm", arch__tie_embeddings=True,
                 serve__host_kv_blocks=None, serve__batch_buckets=[1, 2, 4],
                 traffic__loop="open", traffic__rate=4.0,
                 traffic__pool=24, traffic__warm_batches=[1, 2],
                 traffic__output_len=dict(dist="uniform", min=16, max=32),
                 traffic__warmup_s=2.0)


def _chat_cell():
    cell = tiny_cell(**QWEN_TINY)
    return dataclasses.replace(
        cell, name="tiny.chat", limits=dict(cell.limits, checked_tokens=150),
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("output_tok_s", "tokens/s"), ("ttft_p80_s", "s"),
            ("itl_p50_ms", "ms"), ("setup_s", "s"))])


def test_tiny_qwen_cell_under_the_pooled_open_loop(capsys):
    """The chat cell's path at a tiny size: served through the Router with
    pooled open arrivals, the reference passes what was served and the
    float8 control, in the program's place, fails the same check."""
    cell = _chat_cell()
    arch = cell.config["arch"]
    seen = {}

    def keep(params, sample):
        for who, fn in (("program", dense.served_gaps),
                        ("control", dense.control_gaps)):
            gaps = np.concatenate([fn(params, arch, p, s) for p, s in sample])
            seen[who] = (gaps.max(), gaps.mean())
    result = run_tiny(cell, seed=2**32 + 21, on_sample=keep)
    out = capsys.readouterr().out
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 20 and result["failed"] == 0
    assert set(result["metrics"]) == {"output_tok_s", "ttft_p80_s",
                                      "itl_p50_ms", "setup_s"}
    assert 0 < result["metrics"]["ttft_p80_s"]["value"] < 6.0
    assert result["metrics"]["itl_p50_ms"]["value"] > 0
    # the generator's lateness, over the requests due in the window
    load = next(line.split() for line in out.splitlines()
                if line.startswith("load:"))
    assert int(load[load.index("due_in_window") + 1]) == result["attempted"]
    assert float(load[load.index("lateness_max_ms") + 1]) >= 0
    limits = (result["checks"]["token_gap_max"]["limit"],
              result["checks"]["token_gap_mean"]["limit"])
    for program, limit, control in zip(seen["program"], limits,
                                       seen["control"]):
        assert program <= limit < control


def _state_unchanged(orig):
    def step(self, params, cache, token, cache_len, active=None):
        logits, _ = orig(self, params, cache, token, cache_len, active)
        return logits, cache
    return step


def _odd_rows_dropped(orig):
    """Half of the batch left out: each odd row is given the logits of the
    even row before it. Slot 1 holds a request whenever two run at once,
    which an open loop's arrivals make happen in every window."""
    def step(self, params, cache, token, cache_len, active=None):
        logits, new = orig(self, params, cache, token, cache_len, active)
        even = jnp.repeat(logits[0::2], 2, axis=0)[:logits.shape[0]]
        return even, new
    return step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_under_the_open_loop_is_not_correct(
        fault, monkeypatch):
    if fault == "token_altered":
        orig = engine_mod._sample_token

        def sample(row, *, pos, vocab_size, **kw):
            tok = orig(row, pos=pos, vocab_size=vocab_size, **kw)
            return (tok + 1) % vocab_size if pos % 7 == 0 else tok
        monkeypatch.setattr(engine_mod, "_sample_token", sample)
    else:
        wrap = (_state_unchanged if fault == "state_unchanged"
                else _odd_rows_dropped)
        monkeypatch.setattr(LM, "decode_step", wrap(LM.decode_step))
    result = run_tiny(_chat_cell(), seed=2**31 + 77, seconds=4.0)
    assert not result["correct"]
    gap = result["checks"]["token_gap_max"]["value"]
    assert gap != gap or gap > 1e-2       # nothing to compare, or wrong
