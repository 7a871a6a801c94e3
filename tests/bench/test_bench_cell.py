"""A whole run on the CPU, past the harness's look for a chip: a tiny cell
defined only here serves through the Router with offload, preemption and a
disk tier, and its check passes; with the timed path broken underneath, or
with the lower-precision control in the program's place, the check fails.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import ARCH, run_tiny, tiny_cell
from bench.models import dense
from repro.configs.base import ArchConfig
from repro.models import LM, build_model
from repro.serve import engine as engine_mod
from repro.serve import naive_generate


@pytest.mark.parametrize("norm,eps,kv_heads,bias", [
    ("rmsnorm", 1e-6, 2, True), ("layernorm_np", 1e-5, 4, False)])
def test_reference_matches_the_program_in_float32(norm, eps, kv_heads, bias):
    arch = dict(ARCH, norm=norm, norm_eps=eps, n_kv_heads=kv_heads,
                qkv_bias=bias)
    params = dense.make_params(arch, 11)
    fields = ArchConfig.__dataclass_fields__
    model = build_model(ArchConfig(
        name="t", **{k: v for k, v in arch.items() if k in fields}))
    prompt = np.random.default_rng(0).integers(0, 512, 37).tolist()
    out, rows = naive_generate(model, params, prompt, max_new=12,
                               max_len=128, return_logits=True)
    ref = np.asarray(dense.reference_rows(params, arch, prompt, out))
    assert np.abs(np.stack(rows) - ref).max() < 1e-4
    assert dense.served_gaps(params, arch, prompt, out).max() < 1e-4


def test_tiny_cell_serves_correctly_through_every_tier(capsys):
    result = run_tiny()
    out = capsys.readouterr().out
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"output_tok_s", "itl_p99_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    tiers = dict(zip(*[iter(out.split("tiers: ")[1].split()[:12])] * 2))
    for key in ("swaps", "reload_bytes", "disk_load_bytes"):
        assert float(tiers[key]) > 0, tiers
    assert list(result)[-1] == "checks"


def test_traced_tiny_run_reads_the_per_layer_metrics():
    result = run_tiny(trace=True, seed=3)
    assert result["correct"], result["checks"]
    got = set(result["metrics"])
    for name in ("stall_share", "kv_moved_bytes_per_tok", "decode_roofline",
                 "mfu", "device_idle_share",
                 "compiles_in_window", "paging_device_share"):
        assert name in got
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert result["breakdown"]["device_ops"]
    assert len(result["breakdown"]["idle_gaps"]) <= 10


def _stale_state(orig):
    def step(self, params, cache, token, cache_len, active=None):
        logits, _ = orig(self, params, cache, token, cache_len, active)
        return logits, cache
    return step


def _half_batch(orig):
    def step(self, params, cache, token, cache_len, active=None):
        logits, new = orig(self, params, cache, token, cache_len, active)
        half = max(logits.shape[0] // 2, 1)
        keep = jnp.concatenate([logits[:half]] * 2)[:logits.shape[0]]
        return keep, new
    return step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    if fault == "token_altered":
        orig = engine_mod._sample_token

        def sample(row, *, pos, vocab_size, **kw):
            tok = orig(row, pos=pos, vocab_size=vocab_size, **kw)
            return (tok + 1) % vocab_size if pos % 7 == 0 else tok
        monkeypatch.setattr(engine_mod, "_sample_token", sample)
    else:
        wrap = _stale_state if fault == "state_unchanged" else _half_batch
        monkeypatch.setattr(LM, "decode_step", wrap(LM.decode_step))
    result = run_tiny(seed=5)
    assert not result["correct"]
    assert result["checks"]["token_gap_max"]["value"] > 1e-2


def test_the_float8_control_fails_the_check():
    """The reference in float8 in the program's place: at the same prompts
    and tokens, the tokens it puts first lie below the reference's best by
    far more than the program's served tokens do, at the widest and on
    the mean."""
    seen = {}

    def keep(params, sample):
        for who, fn in (("program", dense.served_gaps),
                        ("control", dense.control_gaps)):
            gaps = np.concatenate([fn(params, ARCH, p, s) for p, s in sample])
            seen[who] = (gaps.max(), gaps.mean())
    result = run_tiny(seed=9, on_sample=keep)
    assert result["correct"]
    limits = (result["checks"]["token_gap_max"]["limit"],
              result["checks"]["token_gap_mean"]["limit"])
    for program, limit, control in zip(seen["program"], limits,
                                       seen["control"]):
        assert program <= limit < control


def test_a_config_and_mix_defined_only_here_need_no_edit():
    cell = tiny_cell(arch__n_kv_heads=4, arch__qkv_bias=False,
                     serve__host_kv_blocks=None,
                     traffic__loop="open", traffic__rate=3.0)
    result = run_tiny(cell, seed=13)
    assert result["correct"], result["checks"]
    assert result["metrics"]["output_tok_s"]["value"] > 0
