"""RWKV6 chunked-WKV Pallas TPU kernel.

Grid (B, H, n_chunks), chunks innermost; per-(batch, head) WKV state [P, P]
carried in VMEM scratch. The per-channel decay requires the [c, c, P]
exponent tensor — kept entirely in VMEM by choosing a small chunk (32), all
exponents non-positive (differences of cumulative log-decays), mirroring
:func:`repro.models.rwkv.wkv6_chunked`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_scr, *,
                 chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)             # [c, P]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)           # [c, P] (log decay ≤ 0)
    u = u_ref[pl.program_id(1)].astype(jnp.float32)   # this head's bonus [P]

    lcw = jnp.cumsum(lw, axis=0)                    # [c, P]
    prev = lcw - lw
    # intra-chunk A[t,s] = Σ_p r_t k_s e^{prev_t - lcw_s}, s < t
    diff = prev[:, None, :] - lcw[None, :, :]       # [c, c, P] ≤ 0 masked
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    E = jnp.exp(jnp.where(tri[..., None], diff, -1e30))
    A = jnp.einsum("tp,tsp,sp->ts", r, E, k,
                   preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # diagonal bonus
    du = jnp.sum(r * u[None, :] * k, axis=-1)       # [c]
    y = y + du[:, None] * v
    # incoming state
    y = y + jax.lax.dot_general(r * jnp.exp(prev), s_scr[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state passing
    tailw = jnp.exp(lcw[-1:, :] - lcw)              # [c, P] ≤ 1
    upd = jax.lax.dot_general(k * tailw, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [P, P]
    s_scr[...] = jnp.exp(lcw[-1])[:, None] * s_scr[...] + upd


def wkv6_kernel(r, k, v, lw, u, *, chunk: int = 32,
                interpret: bool = False):
    """r/k/v/lw: [B, S, H, P] (lw = log decay, ≤0); u: [H, P].
    Returns y: [B, S, H, P]. S must be chunk-padded by the wrapper."""
    B, S, H, P = r.shape
    assert S % chunk == 0
    nc = S // chunk
    from jax.experimental.pallas import tpu as pltpu
    tr = lambda t: t.transpose(0, 2, 1, 3)          # [B, H, S, P]
    y = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=(B, H, nc),
        in_specs=[pl.BlockSpec((1, 1, chunk, P),
                               lambda b, h, ic: (b, h, ic, 0))] * 4
        # u whole: a (1, P) block breaks the 8-sublane tiling rule
        + [pl.BlockSpec((H, P), lambda b, h, ic: (0, 0))],
        out_specs=pl.BlockSpec((1, 1, chunk, P),
                               lambda b, h, ic: (b, h, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), r.dtype),
        scratch_shapes=[pltpu.VMEM((P, P), jnp.float32)],
        interpret=interpret,
    )(tr(r), tr(k), tr(v), tr(lw), u)
    return y.transpose(0, 2, 1, 3)
