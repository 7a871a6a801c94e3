"""The dense decoder family as the benchmark sees it: its weights, its plain
reference, the reference's lower-precision control, and the work a step
needs.

The weights are made here, from the seed, in the layout the program's
``LM`` takes (per-layer leaves stacked on a leading layer axis). The
reference reads the same weights and nothing else of the program: it is
written from the published description of a pre-norm decoder with rotary
positions (NeoX half rotation), grouped-query attention, a SwiGLU MLP, and
either RMSNorm or a LayerNorm without affine parameters, in float32 at the
highest matmul precision. Tied embeddings are used as published: the
logits are the final hidden state times the transposed embedding.

The control is the same reference with every linear layer computed from
float8 (e4m3) inputs, scaled per output channel for weights and per token
for activations: the precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 512          # query rows per attention block, and the length quantum
F8_MAX = 448.0       # largest finite float8_e4m3fn


def dims(arch: dict) -> dict:
    d, h = arch["d_model"], arch["n_heads"]
    return dict(L=arch["n_layers"], D=d, H=h, K=arch["n_kv_heads"],
                Dh=d // h, F=arch["d_ff"], V=arch["vocab_size"],
                Vp=-(-arch["vocab_size"] // 128) * 128)


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from a seed of any size."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


# --------------------------------------------------------------- weights
def make_params(arch: dict, seed: int, device=None) -> dict:
    """Random weights in the program's layout, made on the device in one
    jitted call, in bfloat16. Biases and norm gains are random too, so the
    reference checks that the program applies them."""
    g = dims(arch)
    L, D, H, K, Dh, F, Vp = (g[k] for k in ("L", "D", "H", "K", "Dh", "F",
                                              "Vp"))
    dt = jnp.dtype(arch["dtype"])
    rms = arch["norm"] == "rmsnorm"

    def init(key):
        keys = iter(jax.random.split(key, 16))

        def normal(shape, std, mean=0.0):
            x = jax.random.normal(next(keys), shape, jnp.float32)
            return (mean + std * x).astype(dt)

        attn = {"wq": normal((L, D, H * Dh), D ** -0.5),
                "wk": normal((L, D, K * Dh), D ** -0.5),
                "wv": normal((L, D, K * Dh), D ** -0.5),
                "wo": normal((L, H * Dh, D), (H * Dh) ** -0.5)}
        if arch["qkv_bias"]:
            attn["bq"] = normal((L, H * Dh), 0.1)
            attn["bk"] = normal((L, K * Dh), 0.1)
            attn["bv"] = normal((L, K * Dh), 0.1)
        layers = {"attn": attn,
                  "mlp": {"wi_gate": normal((L, D, F), D ** -0.5),
                          "wi_up": normal((L, D, F), D ** -0.5),
                          "wo": normal((L, F, D), F ** -0.5)}}
        embed = normal((Vp, D), 0.02)
        params = {"embed": embed, "layers": layers,
                  "unembed": (embed.T if arch["tie_embeddings"]
                              else normal((D, Vp), D ** -0.5))}
        if rms:
            layers["ln1_g"] = normal((L, D), 0.1, 1.0)
            layers["ln2_g"] = normal((L, D), 0.1, 1.0)
            params["ln_f_g"] = normal((D,), 0.1, 1.0)
        return params

    out = (None if device is None
           else jax.sharding.SingleDeviceSharding(device))
    params = jax.jit(init, out_shardings=out)(
        jax.random.PRNGKey(seed32(seed)))
    return jax.block_until_ready(params)


# ------------------------------------------------------------- reference
def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(arch, x, g):
    eps = arch["norm_eps"]
    if arch["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """x [T, heads, Dh]: rotate the two halves of each head."""
    T, _, dh = x.shape
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # [T, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention, q [T, H, Dh] against k, v [T, K, Dh]; query head
    h reads key head h // (H / K). Computed in blocks of query rows."""
    T, H, dh = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    key_pos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * CHUNK, CHUNK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        s = s / math.sqrt(dh)
        qpos = i * CHUNK + jnp.arange(CHUNK)
        s = jnp.where(key_pos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(T // CHUNK))
    return out.reshape(T, H, dh)


def _hidden(params, arch, tokens, fp8):
    """Final normed hidden state [T, D] of one token sequence."""
    g = dims(arch)
    H, K, Dh = g["H"], g["K"], g["Dh"]
    T = tokens.shape[0]
    f32 = jnp.float32
    h = params["embed"][tokens].astype(f32)

    def layer(h, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        a = lp["attn"]
        x = _norm(arch, h, lp.get("ln1_g"))
        q = _linear(x, a["wq"], fp8) + a.get("bq", 0.0)
        k = _linear(x, a["wk"], fp8) + a.get("bk", 0.0)
        v = _linear(x, a["wv"], fp8) + a.get("bv", 0.0)
        q = _rope(q.reshape(T, H, Dh), arch["rope_theta"])
        k = _rope(k.reshape(T, K, Dh), arch["rope_theta"])
        o = _attention(q, k, v.reshape(T, K, Dh)).reshape(T, H * Dh)
        h = h + _linear(o, a["wo"], fp8)
        x = _norm(arch, h, lp.get("ln2_g"))
        m = lp["mlp"]
        y = jax.nn.silu(_linear(x, m["wi_gate"], fp8)) \
            * _linear(x, m["wi_up"], fp8)
        return h + _linear(y, m["wo"], fp8), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    return _norm(arch, h, params.get("ln_f_g", jnp.ones((), f32)))


def _logits(params, arch, h, fp8):
    """Logits over the real vocabulary from hidden rows h [n, D]."""
    V = arch["vocab_size"]
    if arch["tie_embeddings"]:
        w = params["embed"][:V].astype(jnp.float32).T
    else:
        w = params["unembed"][:, :V].astype(jnp.float32)
    return _linear(h, w, fp8)


def _rows(params, arch, tokens, out_pos, fp8):
    h = _hidden(params, arch, tokens, fp8)
    return _logits(params, arch, h[out_pos], fp8)


_ROWS = jax.jit(_rows, static_argnames=("arch", "fp8"))


class _Arch(dict):
    """A hashable view of a configuration's arch entry (a jit static)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _inputs(prompt, served):
    """The sequence the served tokens were sampled along (prompt, then
    every served token but the last), padded to a multiple of CHUNK, and
    the position whose logits chose each served token (padded to CHUNK)."""
    seq = list(prompt) + list(served[:-1])
    T = -(-len(seq) // CHUNK) * CHUNK
    n = len(served)
    n_pad = -(-n // CHUNK) * CHUNK
    tokens = np.zeros(T, np.int32)
    tokens[:len(seq)] = seq
    pos = np.zeros(n_pad, np.int32)
    pos[:n] = len(prompt) - 1 + np.arange(n)
    return tokens, pos


def reference_rows(params, arch, prompt, served, *, fp8=False):
    """Logit rows [len(served), vocab] that chose each served token, from
    the reference (``fp8``: from the control), teacher-forced on the
    served tokens."""
    tokens, pos = _inputs(prompt, served)
    rows = _ROWS(params, _Arch(arch), jnp.asarray(tokens), jnp.asarray(pos),
                 fp8=fp8)
    return rows[:len(served)]


@jax.jit
def _gaps(ref_rows, chosen):
    """How far below the reference's best each chosen token's logit lies."""
    pick = jnp.take_along_axis(ref_rows, chosen[:, None], axis=1)[:, 0]
    return jnp.max(ref_rows, axis=1) - pick


def served_gaps(params, arch, prompt, served) -> np.ndarray:
    """Per served token: the reference's best logit minus the reference's
    logit of the token the program served."""
    ref = reference_rows(params, arch, prompt, served)
    return np.asarray(_gaps(ref, jnp.asarray(served, jnp.int32)))


def control_gaps(params, arch, prompt, served) -> np.ndarray:
    """Per position: the reference's best logit minus the reference's
    logit of the token the control puts first, at the same prompts and
    served tokens."""
    ref = reference_rows(params, arch, prompt, served)
    ctl = reference_rows(params, arch, prompt, served, fp8=True)
    return np.asarray(_gaps(ref, jnp.argmax(ctl, axis=1).astype(jnp.int32)))


# ------------------------------------------------------------ needed work
def _matmul_params(g: dict) -> int:
    """Weights one token multiplies through, per token, without the
    embedding lookup and the unembedding."""
    D, H, K, Dh, F = g["D"], g["H"], g["K"], g["Dh"], g["F"]
    return g["L"] * (D * H * Dh + 2 * D * K * Dh + H * Dh * D + 3 * D * F)


def weight_bytes(arch: dict) -> int:
    """Bytes of weights a step reads: every layer, the norms, and the
    unembedding; the embedding table is gathered by row, not read."""
    g = dims(arch)
    item = jnp.dtype(arch["dtype"]).itemsize
    extra = 0
    if arch["qkv_bias"]:
        extra += g["L"] * (g["H"] + 2 * g["K"]) * g["Dh"]
    if arch["norm"] == "rmsnorm":
        extra += (2 * g["L"] + 1) * g["D"]
    return item * (_matmul_params(g) + g["D"] * g["V"] + extra)


def kv_token_bytes(arch: dict) -> int:
    g = dims(arch)
    return 2 * g["L"] * g["K"] * g["Dh"] * jnp.dtype(arch["dtype"]).itemsize


def decode_work(arch: dict, lens) -> tuple[float, float]:
    """FLOPs and HBM bytes one decode step needs: rows ``len(lens)``, row
    ``i`` attending ``lens[i]`` positions (the new token included). Bytes:
    the weights once, each row's live KV read, the new token's KV written,
    the embedding rows gathered and the logits written."""
    g = dims(arch)
    item = jnp.dtype(arch["dtype"]).itemsize
    rows, ctx = len(lens), float(sum(lens))
    flops = (2.0 * rows * (_matmul_params(g) + g["D"] * g["V"])
             + 4.0 * g["L"] * g["H"] * g["Dh"] * ctx)
    nbytes = (weight_bytes(arch) + kv_token_bytes(arch) * (ctx + rows)
              + item * rows * (g["D"] + g["V"]))
    return flops, float(nbytes)


def prefill_work(arch: dict, lens) -> tuple[float, float]:
    """FLOPs and HBM bytes a prefill of prompts ``lens`` needs: every real
    (unpadded) prompt token through every layer, causal attention over the
    real lengths, and logits at each prompt's last position only."""
    g = dims(arch)
    item = jnp.dtype(arch["dtype"]).itemsize
    rows, tokens = len(lens), float(sum(lens))
    causal = float(sum(n * (n + 1) for n in lens))
    flops = (2.0 * tokens * _matmul_params(g)
             + 2.0 * rows * g["D"] * g["V"]
             + 2.0 * g["L"] * g["H"] * g["Dh"] * causal)
    nbytes = (weight_bytes(arch) + kv_token_bytes(arch) * tokens
              + item * (tokens * g["D"] + rows * g["V"]))
    return flops, float(nbytes)
