"""A tiny cell, defined here and nowhere else, that the benchmark's tests
drive end to end on the CPU: a two-layer dense model with grouped-query
attention and QKV biases in float32, served with offload, preemption and a
disk tier."""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

# made-up peaks: a CPU run's rooflines are arithmetic checks, never speeds
FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

ARCH = dict(family="dense", n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
            d_ff=256, vocab_size=512, norm="rmsnorm", norm_eps=1e-6,
            mlp="swiglu", qkv_bias=True, rope_theta=1e4, tie_embeddings=True,
            dtype="float32")
SERVE = dict(max_len=128, block_size=32, hot_window=32, batch_buckets=[1, 4, 8],
             offload=True, preempt_every=4, reload_policy="critical-path",
             host_kv_blocks=6, temperature=0.0, dma_latency=0.0, h2d_bw=None,
             d2h_bw=None, disk_bw=None)
TRAFFIC = dict(loop="closed", clients=12,
               prompt_len=dict(dist="lognormal", median=40, sigma=0.4, min=20,
                               max=60),
               output_len=dict(dist="uniform", min=6, max=12), pool=64,
               warm_batches=[1], warmup_s=6.0)
# float32 in program and reference: they agree to rounding, far below this
LIMITS = {"token_gap_max": 1e-4, "token_gap_mean": 1e-5, "checked_tokens": 40}
# the tiny cell reads every metric that has a reader, whichever cells use it
E2E = {"output_tok_s": "tokens/s", "itl_p99_ms": "ms", "setup_s": "s"}
PER_LAYER = sorted(p.stem for p in (ROOT / "bench/metrics").glob("*.py")
                   if p.stem not in E2E)


def tiny_cell(**changes) -> harness.Cell:
    config = dict(name="tiny", model="dense", arch=dict(ARCH),
                  serve=dict(SERVE))
    traffic = dict(TRAFFIC)
    for key, value in changes.items():
        part, _, field = key.partition("__")
        {"arch": config["arch"], "serve": config["serve"],
         "traffic": traffic}[part][field] = value
    return harness.Cell(name="tiny.swap", chips=1, config=config,
                        traffic=traffic,
                        end_to_end=[{"name": n, "unit": u}
                                    for n, u in E2E.items()],
                        per_layer=[{"name": n, "unit": "-"}
                                   for n in PER_LAYER],
                        limits=dict(LIMITS))


def run_tiny(cell=None, *, seed=2**31 + 7, seconds=6.0, trace=False,
             **kw) -> dict:
    return harness.run_cell(cell or tiny_cell(), seed=seed, seconds=seconds,
                            trace=trace, t_start=time.monotonic(),
                            require_chips=False, peaks=FAKE_PEAKS, **kw)
