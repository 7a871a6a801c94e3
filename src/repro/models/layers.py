"""Transformer building blocks (pure functional JAX).

Everything here is shape-polymorphic, scan-friendly and GSPMD-compatible.
Attention uses an online-softmax *blockwise* formulation (no [S, S]
materialization — mandatory for the 32k prefill shapes). The Pallas flash
kernel in :mod:`repro.kernels.flash_attention` is not wired in here.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..sharding.rules import constrain

Array = jax.Array

# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rmsnorm(x: Array, gamma: Array | None, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y if gamma is None else y * gamma


def layernorm(x: Array, gamma: Array | None = None, beta: Array | None = None,
              eps: float = 1e-5) -> Array:
    """Non-parametric when gamma/beta are None (OLMo §'non-parametric LN')."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    if gamma is not None:
        y = y * gamma
    if beta is not None:
        y = y + beta
    return y


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------
def rope(x: Array, positions: Array, theta: float = 1e4) -> Array:
    """x: [..., S, H, Dh]; positions: [..., S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32)
                    / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def _gqa_scores(q: Array, k: Array, scale: float) -> Array:
    """q: [B,Sq,Hq,Dh] grouped as [B,Sq,Hkv,G,Dh]; k: [B,Skv,Hkv,Dh]."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale


def blockwise_attention(q: Array, k: Array, v: Array, *, causal: bool,
                        q_offset: Array | int = 0,
                        block_kv: int = 1024) -> Array:
    """Online-softmax attention over KV blocks — O(block) memory, no [S,S]
    intermediate (flash-attention algorithm expressed in XLA; the Pallas
    kernel in :mod:`repro.kernels.flash_attention` is the TPU-tiled twin).

    q: [B, Sq, Hq, Dh], k/v: [B, Skv, Hkv, Dh] with Hq % Hkv == 0.
    ``q_offset``: absolute position of q[0] (for causal masking of a suffix
    chunk against a longer KV, e.g. chunked prefill / decode)."""
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Sq, Hkv, G, Dh)

    nblk = max(1, (Skv + block_kv - 1) // block_kv)
    pad = nblk * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block_kv, Hkv, Dh)
    vb = v.reshape(B, nblk, block_kv, Hkv, Dh)

    q_pos = jnp.arange(Sq) + q_offset                       # [Sq]

    def step(carry, blk):
        m, l, o = carry
        kj, vj, j = blk
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kj) * scale  # [B,Hkv,G,Sq,bk]
        kv_pos = j * block_kv + jnp.arange(block_kv)
        mask = jnp.broadcast_to((kv_pos < Skv)[None, :], (Sq, block_kv))
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(mask[None, None, None], s.astype(jnp.float32), -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, Hkv, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    o0 = jnp.zeros((B, Hkv, G, Sq, Dh), jnp.float32)
    # checkpoint the block step: backward recomputes the [.., Sq, bk] score
    # tile instead of storing one per block (flash-attention recompute).
    (m, l, o), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, o0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nblk)))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dh).astype(q.dtype)


def decode_attention(q: Array, k_cache: Array, v_cache: Array,
                     cache_len: Array | int) -> Array:
    """One-token attention against a [B, Smax, Hkv, Dh] cache."""
    B, Sq, Hq, Dh = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Sq, Hkv, G, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache) * scale
    pos = jnp.arange(Smax)
    mask = pos[None, :] < jnp.asarray(cache_len).reshape(-1, 1)  # [B|1, Smax]
    s = jnp.where(mask[:, None, None, None, :], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_cache.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dh).astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class AttnParamsSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    qkv_bias: bool = False

    def init(self, key: Array, dtype=jnp.float32) -> dict:
        d, H, K, Dh = self.d_model, self.n_heads, self.n_kv_heads, self.d_head
        k1, k2, k3, k4 = jax.random.split(key, 4)
        s = 1.0 / math.sqrt(d)
        p = {
            "wq": jax.random.normal(k1, (d, H * Dh), dtype) * s,
            "wk": jax.random.normal(k2, (d, K * Dh), dtype) * s,
            "wv": jax.random.normal(k3, (d, K * Dh), dtype) * s,
            "wo": jax.random.normal(k4, (H * Dh, d), dtype) * s,
        }
        if self.qkv_bias:
            p["bq"] = jnp.zeros((H * Dh,), dtype)
            p["bk"] = jnp.zeros((K * Dh,), dtype)
            p["bv"] = jnp.zeros((K * Dh,), dtype)
        return p


def attention_block(p: dict, x: Array, *, n_heads: int, n_kv_heads: int,
                    d_head: int, positions: Array, causal: bool = True,
                    rope_theta: float = 1e4, kv: Array | None = None,
                    block_kv: int = 1024) -> Array:
    """Self- (or cross-, when ``kv`` given) attention with RoPE + GQA."""
    B, S, _ = x.shape
    src = x if kv is None else kv
    Skv = src.shape[1]
    q = (x @ p["wq"] + p.get("bq", 0)).reshape(B, S, n_heads, d_head)
    k = (src @ p["wk"] + p.get("bk", 0)).reshape(B, Skv, n_kv_heads, d_head)
    v = (src @ p["wv"] + p.get("bv", 0)).reshape(B, Skv, n_kv_heads, d_head)
    # head-sharded attention (Megatron TP): keeps the whole attention local
    # per device; without it GSPMD gathers SP-sharded K/V per block
    # (§Perf iteration B2; constrain no-ops when heads don't divide)
    q = constrain(q, ("pod", "data"), None, "model", None, require="model")
    k = constrain(k, ("pod", "data"), None, "model", None, require="model")
    v = constrain(v, ("pod", "data"), None, "model", None, require="model")
    if kv is None and rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    o = blockwise_attention(q, k, v, causal=causal and kv is None,
                            block_kv=block_kv)
    o = constrain(o, ("pod", "data"), None, "model", None, require="model")
    return o.reshape(B, S, n_heads * d_head) @ p["wo"]


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def swiglu_mlp(p: dict, x: Array) -> Array:
    return (jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])) @ p["wo"]


def gelu_mlp(p: dict, x: Array) -> Array:
    return jax.nn.gelu(x @ p["wi"] + p.get("bi", 0), approximate=True) \
        @ p["wo"] + p.get("bo", 0)


def mlp_init(key: Array, d_model: int, d_ff: int, kind: str = "swiglu",
             dtype=jnp.float32, bias: bool = False) -> dict:
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"wi_gate": jax.random.normal(ks[0], (d_model, d_ff), dtype) * s_in,
                "wi_up": jax.random.normal(ks[1], (d_model, d_ff), dtype) * s_in,
                "wo": jax.random.normal(ks[2], (d_ff, d_model), dtype) * s_out}
    p = {"wi": jax.random.normal(ks[0], (d_model, d_ff), dtype) * s_in,
         "wo": jax.random.normal(ks[1], (d_ff, d_model), dtype) * s_out}
    if bias:
        p["bi"] = jnp.zeros((d_ff,), dtype)
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


# --------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, capacity-bounded dropless-ish)
# --------------------------------------------------------------------------
def moe_init(key: Array, d_model: int, d_expert: int, n_experts: int,
             dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_expert)
    return {
        "router": jax.random.normal(ks[0], (d_model, n_experts),
                                    jnp.float32) * s_in,
        "wi_gate": jax.random.normal(ks[1], (n_experts, d_model, d_expert),
                                     dtype) * s_in,
        "wi_up": jax.random.normal(ks[2], (n_experts, d_model, d_expert),
                                   dtype) * s_in,
        "wo": jax.random.normal(ks[3], (n_experts, d_expert, d_model),
                                dtype) * s_out,
    }


def moe_block(p: dict, x: Array, *, n_experts: int, top_k: int,
              capacity_factor: float | None = 1.25) -> tuple[Array, Array]:
    """Top-k token-choice routing with per-expert capacity (GShard-style).

    Tokens are dispatched to [E, C, D] buffers with one-hot combines, so the
    expert compute is a *grouped* einsum whose FLOPs equal the active-expert
    FLOPs (E·C·D·F with E·C ≈ tokens·top_k), not a dense all-experts pass —
    this keeps the roofline's MODEL_FLOPS/HLO_FLOPs ratio honest.
    ``capacity_factor=None`` → dropless (C = T·top_k; used for decode and
    for exactness tests). Returns (output, aux_loss)."""
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    logits = (xt.astype(jnp.float32) @ p["router"])            # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)        # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    if capacity_factor is None:
        C = T * top_k                                  # dropless
    else:
        C = max(1, int(capacity_factor * T * top_k / n_experts))
    # position of each (token, k) within its expert queue
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # [T,k,E]
    flat = onehot.reshape(T * top_k, n_experts)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat)                # [Tk, E]
    pos = (pos_in_expert * flat).sum(-1).reshape(T, top_k)
    keep = pos < C
    gate_vals = gate_vals * keep

    # dispatch: [E, C, D]
    e_flat = expert_idx.reshape(-1)
    pos_flat = jnp.where(keep.reshape(-1), pos.reshape(-1), C)  # drop → C
    buf = jnp.zeros((n_experts, C + 1, D), x.dtype)
    tok_rep = jnp.repeat(jnp.arange(T), top_k)
    buf = buf.at[e_flat, pos_flat].add(xt[tok_rep])
    # experts over 'model' (EP). NOTE (§Perf iteration D1, REFUTED): also
    # sharding capacity over 'data' should cut expert FLOPs 16×, but GSPMD
    # cannot lower the global-index scatter into a data-sharded buffer —
    # collectives exploded ~1000×. Proper fix: shard_map dispatch with local
    # capacity + explicit all-to-all (future work; see EXPERIMENTS.md §Perf).
    buf = constrain(buf[:, :C], "model", None, None)

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"])
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["wi_up"])
    y_e = jnp.einsum("ecf,efd->ecd", h, p["wo"])               # [E, C, D]
    y_e = constrain(y_e, "model", None, None)

    # combine
    y_flat = y_e.reshape(n_experts * C, D)
    gather_idx = jnp.where(keep.reshape(-1), e_flat * C + pos_flat, 0)
    y_tok = y_flat[gather_idx] * gate_vals.reshape(-1, 1).astype(x.dtype)
    y = y_tok.reshape(T, top_k, D).sum(axis=1)

    # load-balance aux loss (Switch): E * mean(frac_tokens * frac_probs)
    frac_tokens = onehot.sum(axis=(0, 1)).astype(jnp.float32) / (T * top_k)
    frac_probs = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(B, S, D), aux


def decode_attention_q8(q: Array, k_cache: Array, v_cache: Array,
                        k_scale: Array, v_scale: Array,
                        cache_len: Array | int) -> Array:
    """decode_attention over an int8 KV cache with per-(token, head) scales
    (KIVI-style, post-RoPE). Dequantization happens inside the einsums so no
    bf16 copy of the cache is materialized."""
    B, Sq, Hq, Dh = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Sq, Hkv, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                   k_cache.astype(jnp.float32)) * scale
    s = s * k_scale.transpose(0, 2, 1)[:, :, None, None, :]   # [B,Hkv,1,1,S]
    pos = jnp.arange(Smax)
    mask = pos[None, :] < jnp.asarray(cache_len).reshape(-1, 1)
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    pv = p * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    o = jnp.einsum("bhgqk,bkhd->bhgqd", pv, v_cache.astype(jnp.float32))
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, Dh).astype(q.dtype)
