"""Numeric op registry for MEMGRAPH execution.

The TURNIP runtime is kernel-agnostic: a TASKGRAPH vertex names an op in this
registry (paper: cuTensor calls / hand-written CUDA kernels; here: numpy
kernels that run on the host). No device kernel is registered: the Pallas
kernels in :mod:`repro.kernels` are not reachable from this registry.

Every op is a pure function ``f(*operand_values, **params) -> np.ndarray``.
Ops must be deterministic given their operands so that any dependency-
respecting execution order yields identical results (floating-point
commutativity of the streaming ``add_into`` accumulation is the one paper-
sanctioned exception, §8 "asynchronous partial summations").
"""
from __future__ import annotations

from typing import Callable

import numpy as np

OPS: dict[str, Callable] = {}


def register(name: str) -> Callable[[Callable], Callable]:
    def deco(fn: Callable) -> Callable:
        if name in OPS:
            raise ValueError(f"op {name!r} already registered")
        OPS[name] = fn
        return fn
    return deco


def get_op(name: str) -> Callable:
    try:
        return OPS[name]
    except KeyError:
        raise KeyError(f"unknown op {name!r}; registered: {sorted(OPS)}") from None


# ---------------------------------------------------------------- basics
@register("copy")
def _copy(x, **_):
    return np.asarray(x)


@register("zeros")
def _zeros(*_, shape=(1,), dtype="float32", **__):
    return np.zeros(shape, np.dtype(dtype))


@register("add")
def _add(x, y, **_):
    return x + y


@register("sum")
def _sum(*xs, **_):
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


@register("mul")
def _mul(x, y, **_):
    return x * y


@register("scale")
def _scale(x, *, alpha=1.0, **_):
    return x * alpha


@register("matmul")
def _matmul(x, y, **_):
    return np.matmul(x, y)


@register("matmul_t")
def _matmul_t(x, y, **_):
    return np.matmul(x, np.swapaxes(y, -1, -2))


@register("relu")
def _relu(x, **_):
    return np.maximum(x, 0)


@register("gelu")
def _gelu(x, **_):
    return 0.5 * x * (1.0 + np.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


@register("silu")
def _silu(x, **_):
    return x / (1.0 + np.exp(-x))


@register("tanh")
def _tanh(x, **_):
    return np.tanh(x)


@register("transpose")
def _transpose(x, **_):
    return np.swapaxes(x, -1, -2)


@register("slice_rows")
def _slice_rows(x, *, start=0, stop=None, **_):
    return x[start:stop]


@register("concat")
def _concat(*xs, axis=0, **_):
    return np.concatenate(xs, axis=axis)


# ---------------------------------------------------------- attention bits
@register("rmsnorm")
def _rmsnorm(x, g, *, eps=1e-6, **_):
    var = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
    return (x / np.sqrt(var + eps) * g).astype(x.dtype)


@register("softmax")
def _softmax(x, **_):
    m = np.max(x, axis=-1, keepdims=True)
    e = np.exp(x - m)
    return e / np.sum(e, axis=-1, keepdims=True)


@register("scores")
def _scores(q, k, *, scale=1.0, causal=False, q_offset=0, **_):
    """q: [Sq, Dh] block at absolute offset q_offset; k: [Skv, Dh]."""
    s = np.matmul(q, np.swapaxes(k, -1, -2)) * scale
    if causal:
        n, m = s.shape[-2], s.shape[-1]
        qpos = np.arange(n) + q_offset
        mask = np.arange(m)[None, :] <= qpos[:, None]
        s = np.where(mask, s, -1e30)
    return s


@register("attn_out")
def _attn_out(p, v, **_):
    return np.matmul(p, v)


@register("lora_delta")
def _lora_delta(x, a, b, *, alpha=16.0, rank=16, **_):
    # x @ A^T @ B^T * (alpha/rank) — LoRA adapter path (paper §8 training)
    return np.matmul(np.matmul(x, np.swapaxes(a, -1, -2)),
                     np.swapaxes(b, -1, -2)) * (alpha / rank)


# ------------------------------------------------- exact backward fragments
@register("matmul_tn")
def _matmul_tn(x, y, **_):
    """x^T @ y — the dW fragment."""
    return np.matmul(np.swapaxes(x, -1, -2), y)


@register("softmax_bwd")
def _softmax_bwd(p, dp, **_):
    """VJP of softmax: p ⊙ (dp − Σ(dp⊙p))."""
    return p * (dp - np.sum(dp * p, axis=-1, keepdims=True))


@register("gelu_bwd")
def _gelu_bwd(x, dy, **_):
    c = 0.7978845608028654
    t = np.tanh(c * (x + 0.044715 * x ** 3))
    dg = 0.5 * (1.0 + t) + 0.5 * x * (1.0 - t ** 2) * c * (1 + 3 * 0.044715 * x ** 2)
    return dy * dg


@register("rmsnorm_bwd")
def _rmsnorm_bwd(x, g, dy, *, eps=1e-6, **_):
    """Exact VJP of rmsnorm wrt x (gamma frozen in LoRA training)."""
    xf = x.astype(np.float64)
    D = xf.shape[-1]
    r = 1.0 / np.sqrt(np.mean(xf ** 2, axis=-1, keepdims=True) + eps)
    dyg = dy.astype(np.float64) * g
    dx = r * dyg - xf * (r ** 3 / D) * np.sum(dyg * xf, axis=-1, keepdims=True)
    return dx.astype(x.dtype)


@register("split_heads")
def _split_heads(x, *, n_heads=1, **_):
    """[T, H*dh] → [H, T, dh] (batched per-head attention math)."""
    T, W = x.shape
    dh = W // n_heads
    return np.ascontiguousarray(x.reshape(T, n_heads, dh).transpose(1, 0, 2))


@register("merge_heads")
def _merge_heads(x, **_):
    """[H, T, dh] → [T, H*dh]."""
    H, T, dh = x.shape
    return np.ascontiguousarray(x.transpose(1, 0, 2).reshape(T, H * dh))


@register("slice_rows_3d")
def _slice_rows_3d(x, *, start=0, stop=None, **_):
    """Slice axis 1 of [H, T, dh]."""
    return x[:, start:stop]
