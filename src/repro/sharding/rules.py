"""Sharding rules: params (TP over 'model' + FSDP over 'data', DP over
'pod'), batches, and decode caches — with divisibility-aware fallbacks so
every assigned architecture × shape lowers on the production meshes.

Strategy (baseline — the §Perf iterations move these around):

* 2-D params ``[in, out]``: contracting/input dim → 'data' (ZeRO-3 style
  shard, all-gathered per layer under scan), output dim → 'model' (Megatron
  TP columns); transposed for output projections.
* MoE expert tensors ``[E, in, out]``: experts → 'model' (expert parallel).
* Activations: only batch is constrained; GSPMD propagates the rest.
* Caches/states: batch → ('pod','data') when divisible; heads → 'model'
  when divisible, else the cache sequence dim → 'model' (decode softmax
  then reduces over a sharded axis — XLA inserts the psum).
"""
from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["param_sharding", "batch_sharding", "cache_sharding",
           "axis_size", "scalar_sharding", "constrain"]


def constrain(x, *spec, require: str | None = None):
    """with_sharding_constraint that degrades gracefully: axes absent from
    the current mesh (or non-divisible dims) are dropped, and without an
    active mesh it is the identity — so model code can annotate activations
    unconditionally (smoke tests run un-meshed on one CPU device).

    ``require='model'``: if that axis cannot be placed on any dim, return x
    UNCONSTRAINED — a constraint whose interesting axis was dropped would
    otherwise pin the tensor to replication, which is far worse than letting
    GSPMD choose (learned the hard way: §Perf iteration B2a)."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not getattr(m, "axis_names", ()):
        return x
    axes = set(m.axis_names)
    fixed = []
    placed: set[str] = set()
    for dim, sp in zip(x.shape, spec):
        cand: Any = sp
        if isinstance(sp, tuple):
            cand = tuple(a for a in sp if a in axes)
            cand = cand if cand else None
        elif sp is not None and sp not in axes:
            cand = None
        if cand is not None:
            n = axis_size(m, *(cand if isinstance(cand, tuple) else (cand,)))
            if n <= 0 or dim % n != 0:
                cand = None
        if cand is not None:
            for a in (cand if isinstance(cand, tuple) else (cand,)):
                placed.add(a)
        fixed.append(cand)
    if require is not None and require not in placed:
        return x
    fixed += [None] * (len(x.shape) - len(fixed))
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def axis_size(mesh: Mesh, *names: str) -> int:
    n = 1
    for nm in names:
        if nm in mesh.shape:
            n *= mesh.shape[nm]
    return n


def _div(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0


def _data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


# ---------------------------------------------------------------- params
# (name, ndim) -> spec template; leading stacked axes get None prepended.
_2D_IN_OUT = ("data", "model")      # [d_in, d_out]
_2D_OUT_IN = ("model", "data")      # [d_out(model-sharded contracting), d_in]

_PARAM_RULES: dict[str, dict[int, tuple]] = {
    # embeddings
    "embed": {2: ("model", "data")},          # [Vp, D] vocab→TP
    "unembed": {2: ("data", "model")},        # [D, Vp]
    # attention
    "wq": {2: _2D_IN_OUT}, "wk": {2: _2D_IN_OUT}, "wv": {2: _2D_IN_OUT},
    "wo": {2: _2D_OUT_IN},
    "bq": {1: ("model",)}, "bk": {1: ("model",)}, "bv": {1: ("model",)},
    # dense mlp
    "wi": {2: _2D_IN_OUT}, "wi_gate": {2: _2D_IN_OUT, 3: ("model", "data", None)},
    "wi_up": {2: _2D_IN_OUT, 3: ("model", "data", None)},
    "bi": {1: ("model",)}, "bo": {1: (None,)},
    # moe
    "router": {2: ("data", None)},
    # rwkv
    "wr": {2: _2D_IN_OUT}, "wg": {2: _2D_IN_OUT}, "cr": {2: _2D_IN_OUT},
    "ck": {2: _2D_IN_OUT}, "cv": {2: _2D_OUT_IN},
    # ssd
    "in_proj": {2: _2D_IN_OUT}, "out_proj": {2: _2D_OUT_IN},
    "conv_w": {2: (None, "model")}, "conv_b": {1: ("model",)},
    "norm_g": {1: ("model",)},
}
# 3D wo = moe experts' output projection [E, F, D]
_PARAM_RULES["wo"][3] = ("model", None, "data")
_PARAM_RULES["wk"][3] = ("model", "data", None)   # (unused; safety)


def _spec_for_param(name: str, shape: tuple[int, ...], mesh: Mesh,
                    stacked_axes: int) -> P:
    base_nd = len(shape) - stacked_axes
    rule = _PARAM_RULES.get(name, {}).get(base_nd)
    if rule is None:
        return P()  # replicate (norm gains, loras, biases, small tensors)
    # verify divisibility; drop axes that don't divide
    spec: list[Any] = [None] * stacked_axes
    for dim, ax in zip(shape[stacked_axes:], rule):
        if ax is None:
            spec.append(None)
        else:
            n = axis_size(mesh, *(ax if isinstance(ax, tuple) else (ax,)))
            spec.append(ax if _div(dim, n) else None)
    return P(*spec)


def param_sharding(param_shapes: Any, mesh: Mesh) -> Any:
    """Tree of NamedShardings for a params pytree (of ShapeDtypeStructs or
    arrays). Layer-stacked arrays are detected by their path containing
    'layers' / 'mamba' / 'enc_layers' / 'dec_layers' / 'shared_adapters'."""
    def one(path, leaf) -> NamedSharding:
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        leafname = names[-1].lstrip("_")
        stacked = 0
        joined = "/".join(names)
        if re.search(r"(^|/)(layers|enc_layers|dec_layers|mamba_tail)(/|$)",
                     joined):
            stacked = 1
        elif re.search(r"(^|/)mamba(/|$)", joined):
            stacked = 2     # [n_groups, group, ...]
        elif leafname == "shared_adapters":
            stacked = 1
        # norm gains inside layers: e.g. ln1_g  → replicated
        if re.match(r"ln\d?_?.*", leafname) or leafname.endswith("_g") \
                and leafname not in _PARAM_RULES:
            spec = P(*([None] * stacked))
        else:
            spec = _spec_for_param(leafname, leaf.shape, mesh, stacked)
        # multiply-invoked shared blocks (zamba): FSDP-sharding their params
        # re-all-gathers them at every unrolled call site — shard over
        # 'model' only (§Perf iteration B1)
        if "/shared/" in f"/{joined}/":
            spec = P(*[(None if ax == "data" else ax) for ax in
                       (tuple(spec) + (None,) * (leaf.ndim - len(spec)))])
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, param_shapes)


# ---------------------------------------------------------------- batches
def batch_sharding(batch_shapes: Any, mesh: Mesh) -> Any:
    """Shard dim 0 (batch) over ('pod','data') when divisible."""
    daxes = _data_axes(mesh)
    n = axis_size(mesh, *daxes)

    def one(leaf) -> NamedSharding:
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        if _div(leaf.shape[0], n):
            return NamedSharding(mesh, P(daxes, *([None] * (leaf.ndim - 1))))
        # try 'data' alone
        if "data" in mesh.shape and _div(leaf.shape[0], mesh.shape["data"]):
            return NamedSharding(mesh, P("data", *([None] * (leaf.ndim - 1))))
        return NamedSharding(mesh, P(*([None] * leaf.ndim)))
    return jax.tree.map(one, batch_shapes)


# ---------------------------------------------------------------- caches
# per-key (head dim, head-feature dim, seq dim) positions in the unstacked
# suffix starting at batch (pos 0); -1 = absent. Fallback order for the
# 'model' axis: heads -> head-feature (Dh) -> sequence. Sharding Dh keeps the
# per-token dynamic_update_slice local - a seq-sharded cache forces a full
# reshard per decode step (Perf iteration A1).
_CACHE_LAYOUT: dict[str, tuple[int, int, int]] = {
    "k": (2, 3, 1), "v": (2, 3, 1),       # [B, S, K, Dh]
    "wkv": (1, -1, -1),                    # [B, H, P, P]
    "ssm": (1, -1, -1),                    # [B, H, P, N]
    "conv": (-1, -1, -1),                  # [B, dconv-1, convdim]
    "ssm_tail": (1, -1, -1), "conv_tail": (-1, -1, -1),
    "tm_shift": (-1, -1, -1), "cm_shift": (-1, -1, -1),
    "enc_out": (-1, -1, 1),                # [B, S_enc, D]
    "k_scale": (2, -1, 1), "v_scale": (2, -1, 1),   # int8-KV scales [B,S,K]
}
_STACK_AXES = {"k": 1, "v": 1, "wkv": 1, "ssm": 2, "conv": 2,
               "ssm_tail": 1, "conv_tail": 1, "tm_shift": 1, "cm_shift": 1,
               "enc_out": 0, "k_scale": 1, "v_scale": 1}


def cache_sharding(cache_shapes: Any, mesh: Mesh) -> Any:
    """Decode caches: batch → ('pod','data') if divisible; heads → 'model'
    if divisible, else the cache sequence dim → 'model' (decode softmax then
    reduces over a sharded axis — XLA inserts the psum)."""
    daxes = _data_axes(mesh)
    nd = axis_size(mesh, *daxes)
    nm = axis_size(mesh, "model")

    def one(path, leaf) -> NamedSharding:
        key = getattr(path[-1], "key", str(path[-1]))
        shape = leaf.shape
        spec: list[Any] = [None] * len(shape)
        if key not in _CACHE_LAYOUT:
            return NamedSharding(mesh, P(*spec))
        stacked = _STACK_AXES[key]
        # zamba kv caches are stacked once even though ssm is stacked twice
        bdim = stacked
        if bdim >= len(shape):
            return NamedSharding(mesh, P(*spec))
        if _div(shape[bdim], nd):
            spec[bdim] = daxes
        elif "data" in mesh.shape and _div(shape[bdim], mesh.shape["data"]):
            spec[bdim] = "data"
        hd, fd, sd = _CACHE_LAYOUT[key]
        for cand in (hd, fd, sd):
            if cand < 0:
                continue
            dim = stacked + cand
            if dim < len(shape) and _div(shape[dim], nm):
                spec[dim] = "model"
                break
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def scalar_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
