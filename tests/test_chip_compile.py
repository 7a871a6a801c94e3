"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX: given a described topology it
compiles for the chip without one, and refuses what the chip would refuse
(misaligned kernel blocks, unsupported kernel primitives, programs larger
than device memory). Nothing here runs on a device, so nothing here says
anything about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One v5e device of a described 2x2 topology, with the persistent
    compile cache off: entries compiled for a described chip cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # noqa: BLE001 — any refusal skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _place(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.fixture(scope="module")
def qwen(one_chip):
    """Qwen2.5-3B at full depth and width, as shapes on the described chip."""
    from repro.configs import get_arch
    from repro.models import build_model
    model = build_model(get_arch("qwen2.5-3b"))
    params = _place(one_chip,
                    jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return model, params


def test_qwen_decode_step_fits_v5e(one_chip, qwen):
    """The serving engine's decode program: 8 slots, 2048-token cache, with
    the active-row mask."""
    model, params = qwen
    cache = _place(one_chip, jax.eval_shape(lambda: model.init_cache(8, 2048)))
    compiled = jax.jit(model.decode_step).lower(
        params, cache, _spec(one_chip, (8, 1), "int32"),
        _spec(one_chip, (8,), "int32"), _spec(one_chip, (8,), "bool"),
    ).compile()
    assert _footprint(compiled) < HBM_BYTES


@pytest.mark.parametrize("seq", [512, 1504])
def test_qwen_prefill_fits_v5e(one_chip, qwen, seq):
    """The engine's batched prefill over 8 prompts; 1504 is the longest
    padded prompt ``chip_smoke.py`` sends."""
    model, params = qwen
    compiled = jax.jit(model.prefill).lower(
        params, _spec(one_chip, (8, seq), "int32"),
        _spec(one_chip, (8,), "int32")).compile()
    assert _footprint(compiled) < HBM_BYTES


def _flash(s):
    from repro.kernels.flash_attention.ops import flash_attention
    return flash_attention, (s((1, 512, 16, 128), "bfloat16"),   # Qwen GQA
                             s((1, 512, 2, 128), "bfloat16"),
                             s((1, 512, 2, 128), "bfloat16"))


def _rmsnorm(s):
    from repro.kernels.rmsnorm.ops import rmsnorm
    return rmsnorm, (s((8, 512, 2048), "bfloat16"), s((2048,), "bfloat16"))


def _moe_gmm(s):
    from repro.kernels.moe_gmm.ops import moe_gmm
    return moe_gmm, (s((32, 128, 1024), "bfloat16"),     # granite-moe widths
                     s((32, 1024, 512), "bfloat16"))


def _ssd_scan(s):
    from repro.kernels.ssd_scan.ops import ssd_scan
    H, P, N = 112, 64, 64                                # zamba2-7b widths
    return ssd_scan, (s((1, 512, H, P), "float32"), s((1, 512, H), "float32"),
                      s((H,), "float32"), s((1, 512, N), "float32"),
                      s((1, 512, N), "float32"))


def _wkv6(s):
    from repro.kernels.rwkv6.ops import wkv6
    H, P = 64, 64                                        # rwkv6-7b widths
    return wkv6, (s((1, 512, H, P), "float32"),) * 4 + (s((H, P), "float32"),)


_NO_CUMSUM = pytest.mark.xfail(
    raises=NotImplementedError, strict=True,
    reason="Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: cumsum")


@pytest.mark.parametrize("build", [
    _flash, _rmsnorm, _moe_gmm,
    pytest.param(_ssd_scan, marks=_NO_CUMSUM),
    pytest.param(_wkv6, marks=_NO_CUMSUM),
], ids=["flash_attention", "rmsnorm", "moe_gmm", "ssd_scan", "rwkv6"])
def test_kernel_compiles_for_v5e(one_chip, build):
    fn, args = build(lambda shape, dtype: _spec(one_chip, shape, dtype))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _footprint(compiled) < HBM_BYTES
