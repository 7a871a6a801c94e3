"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid (B, H, n_chunks): chunks innermost (sequential on TPU), per-(batch,
head) SSM state [P, N] carried in VMEM scratch across chunks; each grid step
computes the intra-chunk quadratic term plus the incoming-state contribution
and updates the state — the same math as the pure-jnp oracle
(:mod:`repro.models.ssm._ssd_chunked`), tiled so the [c, c] decay matrix
lives entirely in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, y_ref, h_scr, *,
                chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)            # [c, P]
    dt = dt_ref[0, 0].astype(jnp.float32)          # [c, 1] (lane-padded)
    a = A_ref[pl.program_id(1)].astype(jnp.float32)   # this head's decay rate
    Bm = B_ref[0].astype(jnp.float32)              # [c, N]
    Cm = C_ref[0].astype(jnp.float32)              # [c, N]

    dA = dt[:, 0] * a                               # [c]  (negative)
    seg = jnp.cumsum(dA)                            # [c]
    # intra-chunk: y[t] = Σ_{s<=t} C_t·B_s dt_s e^{seg_t - seg_s} x_s
    diff = seg[:, None] - seg[None, :]              # [c, c]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(tri, diff, -1e30))
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [c, c]
    w = cb * decay * dt[None, :, 0]                 # [t, s]
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [c, P]
    # incoming state: y += (C_t e^{seg_t}) · h^T   (h: [P, N])
    y = y + jax.lax.dot_general(
        Cm * jnp.exp(seg)[:, None], h_scr[...],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h' = e^{seg_c} h + Σ_s e^{seg_c - seg_s} dt_s x_s B_s^T
    tail = jnp.exp(seg[-1] - seg) * dt[:, 0]        # [c]
    upd = jax.lax.dot_general(x, Bm * tail[:, None],
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [P, N]
    h_scr[...] = jnp.exp(seg[-1]) * h_scr[...] + upd


def ssd_scan_kernel(xh, dt, A, Bm, Cm, *, chunk: int = 128,
                    interpret: bool = False):
    """xh: [B, S, H, P]; dt: [B, S, H] (softplus'ed); A: [H] (negative);
    Bm/Cm: [B, S, N]. Returns y: [B, S, H, P]. S must be chunk-padded by the
    wrapper."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    from jax.experimental.pallas import tpu as pltpu
    xT = xh.transpose(0, 2, 1, 3)                   # [B, H, S, P]
    dtT = dt.transpose(0, 2, 1)[..., None]          # [B, H, S, 1]
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ic: (b, h, ic, 0)),
            # A whole in SMEM: a rank-1 (1,) VMEM block breaks the 128-lane
            # tiling, and the kernel only needs one scalar of it per head
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda b, h, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, ic: (b, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P),
                               lambda b, h, ic: (b, h, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xT, dtT, A, Bm, Cm)
    return y.transpose(0, 2, 1, 3)
