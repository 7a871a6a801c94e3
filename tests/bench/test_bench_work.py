"""The benchmark's yardstick: needed-work functions against hand counts,
the peaks table, and rooflines computed from them."""
import json

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the checkout on sys.path)
from bench import harness
from bench.models import dense

# Qwen2.5-3B (hf Qwen/Qwen2.5-3B): grouped-query attention with two KV
# heads, QKV biases and RMSNorm gains, the parts OLMo-1B has none of
QWEN = dict(family="dense", n_layers=36, d_model=2048, n_heads=16,
            n_kv_heads=2, d_ff=11008, vocab_size=151936, norm="rmsnorm",
            norm_eps=1e-6, mlp="swiglu", qkv_bias=True, rope_theta=1e6,
            tie_embeddings=True, dtype="bfloat16")
OLMO = json.loads((ROOT / "bench/configs/olmo-1b.json").read_text())["arch"]


def test_qwen_weight_bytes_by_hand():
    # per layer: q 2048x2048, k and v 2048x256, o 2048x2048, MLP 3x2048x11008,
    # q/k/v biases 2048+256+256, two norm gains 2x2048; plus the
    # unembedding 2048x151936 and the final norm; bfloat16
    layer = (2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048 + 3 * 2048 * 11008
             + 2048 + 256 + 256 + 2 * 2048)
    want = 2 * (36 * layer + 2048 * 151936 + 2048)
    assert dense.weight_bytes(QWEN) == want
    # the whole model, embedding included, is the 6,794,207,232 B that the
    # program holds (one table more: the embedding)
    assert want + 2 * 151936 * 2048 == 6_794_207_232


def test_kv_bytes_per_token_by_hand():
    assert dense.kv_token_bytes(QWEN) == 2 * 36 * 2 * 128 * 2 == 36_864
    assert dense.kv_token_bytes(OLMO) == 2 * 16 * 16 * 128 * 2 == 131_072


def test_decode_work_by_hand():
    lens = [100, 300]
    flops, nbytes = dense.decode_work(OLMO, lens)
    per_tok = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192) + 2048 * 50304
    attn = 4 * 16 * 16 * 128 * 400          # QK and PV over live lengths
    assert flops == 2 * 2 * per_tok + attn
    want = (dense.weight_bytes(OLMO) + 131_072 * (400 + 2)
            + 2 * 2 * (2048 + 50304))
    assert nbytes == want


def test_prefill_work_counts_real_tokens_causally():
    flops1, bytes1 = dense.prefill_work(OLMO, [10])
    flops2, _ = dense.prefill_work(OLMO, [10, 10])
    per_tok = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    causal = 2 * 16 * 16 * 128 * (10 * 11)
    assert flops1 == 2 * 10 * per_tok + 2 * 2048 * 50304 + causal
    assert flops2 == 2 * flops1
    assert bytes1 == (dense.weight_bytes(OLMO) + 131_072 * 10
                      + 2 * (10 * 2048 + 50304))


def test_peaks_table_and_unknown_chip():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks
    with pytest.raises(KeyError, match="no peaks"):
        harness.load_peaks("TPU v9 imaginary")


class _Trace:
    def __init__(self, times):
        self._times = times

    def program_time(self):
        return self._times


@pytest.mark.parametrize("kind,prefix", [("decode", "jit_decode_step"),
                                         ("prefill", "jit_prefill")])
def test_roofline_is_least_time_over_device_time(kind, prefix):
    rec = (1.0, "replica-0", 5, 700)
    run = harness.Run(
        arch=OLMO, work=dense, peaks=harness.load_peaks("TPU v5 lite"),
        t0=0.0, t1=2.0, setup_s=1.0, sent={}, tokens={}, done={},
        decode=[rec, (1.0, "replica-0", 5, 300)], prefill=[rec],
        stats0={}, stats1={}, compiles=[])
    run.trace = _Trace({prefix: (0.05, 1), "jit_other": (1.0, 9)})
    fn = dense.decode_work if kind == "decode" else dense.prefill_work
    lens = [700, 300] if kind == "decode" else [700]
    flops, nbytes = fn(OLMO, lens)
    least = max(flops / 197e12, nbytes / 819e9)
    assert run.roofline(kind, prefix) == pytest.approx(100 * least / 0.05)
    # one decode step of two rows is bound by bytes; the prefill of 700
    # tokens by FLOPs
    assert (nbytes / 819e9 > flops / 197e12) == (kind == "decode")


def test_roofline_without_trace_or_program_reads_nothing():
    run = harness.Run(arch=OLMO, work=dense, peaks={}, t0=0.0, t1=1.0,
                      setup_s=0.0, sent={}, tokens={}, done={}, decode=[],
                      prefill=[], stats0={}, stats1={}, compiles=[])
    assert run.roofline("decode", "jit_decode_step") is None
    run.trace = _Trace({})
    assert run.roofline("decode", "jit_decode_step") is None
