"""Block-paged KV cache — the serving-side MEMGRAPH memory discipline.

The device cache is the model's dense decode cache (``LM.init_cache``):
every leaf is laid out ``[L, B, S_max, ...]`` with batch *slots* on axis 1
and the token axis (2) divided into fixed ``block_size``-token blocks. Like
the runtime's static extents (paper §4), a ``(slot, block)`` pair names a
fixed byte range for the whole serving run — no allocation happens per
token, and every transfer moves a whole extent.

This class is pure device-side geometry + extent I/O; the owning engine
moves the payloads through a :class:`~repro.core.stores.HostStore` (or,
when ``host_kv_bytes`` bounds the mirror, a
:class:`~repro.core.stores.TieredStore` whose cold blocks continue down to
a file-backed disk tier) on its DMA and disk streams. Blocks are the
offload unit (NEO / SpecOffload direction, PAPERS.md):

* :meth:`read_block`   — device→host snapshot of one block (a d2h payload);
* :meth:`write_block`  — host→device restore of one block (an h2d payload);
* :meth:`restore_slot` — apply a resumed request's blocks to its slot in one
  scatter. The engine's h2d stream copies each block onto the device as
  it reloads, within :attr:`staging_cap`; a block past the cap arrives
  as host arrays and is copied here, on the run loop;
* :meth:`drop_slot`    — zero a slot's extents when its request is swapped
  out, so a missed reload computes on zeros instead of silently reusing
  stale bytes (the serving analogue of ``SlotTable`` read-validation);
* :meth:`scatter_prefill` — write a batched prefill's ``[L, b, S, ...]``
  K/V into freshly admitted slots in one update;
* :meth:`grow` — widen the slot axis to the next batch bucket (the only
  "allocation", and it happens at admission boundaries, never per token).

Host copies of *cold* blocks stay valid for the lifetime of a request —
once a block's token range is fully behind the decode position it is never
rewritten — so a request preempted twice re-offloads only the tail block
that kept growing: the serving analogue of ``build.py``'s
``reuse_host_copy`` (DESIGN.md §7).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PagedKVCache"]


class PagedKVCache:
    """Device-side paged view over a dense decode cache pytree.

    All methods that mutate ``self.cache`` replace leaves functionally and
    must be called with the owning engine's lock held; ``read_block`` only
    reads (jax arrays are immutable, so a snapshot taken under the lock
    stays consistent on a DMA thread)."""

    def __init__(self, model, bucket: int, max_len: int, *,
                 block_size: int = 32, device=None) -> None:
        """``device``: where the cache lives and every host→device copy
        lands (default: the first device JAX sees)."""
        if max_len % block_size:
            raise ValueError("max_len must be a multiple of block_size")
        self.model = model
        self.bucket = bucket
        self.max_len = max_len
        self.block_size = block_size
        self.device = device if device is not None else jax.devices()[0]
        # built on the device itself: a cache made on the default device
        # and then moved would pass through (and briefly double on) it
        self.cache: dict[str, Any] = jax.jit(
            model.init_cache, static_argnums=(0, 1),
            out_shardings=jax.sharding.SingleDeviceSharding(self.device),
        )(bucket, max_len)
        for name, leaf in self.cache.items():
            if leaf.ndim < 3 or leaf.shape[1] != bucket \
                    or leaf.shape[2] != max_len:
                raise ValueError(
                    f"cache leaf {name!r} of shape {leaf.shape} is not "
                    "[L, B, S, ...] token-paged — PagedKVCache supports "
                    "the attention families (dense/moe) only")
        self.n_blocks = max_len // block_size
        # bytes of one (slot, block) extent, summed over leaves (k, v, and
        # int8 scales when present)
        self.block_nbytes = sum(
            leaf.shape[0] * int(np.prod(leaf.shape[3:], dtype=np.int64))
            * block_size * leaf.dtype.itemsize
            for leaf in self.cache.values())

    # ------------------------------------------------------------ geometry
    def n_token_blocks(self, pos: int) -> int:
        """Blocks covering cache positions [0, pos)."""
        return -(-pos // self.block_size)

    def token_range(self, blk: int) -> tuple[int, int]:
        return blk * self.block_size, (blk + 1) * self.block_size

    def leaf_spec(self) -> dict[str, tuple[tuple[int, ...], str]]:
        """Per-leaf (shape, dtype) of ONE block's payload —
        ``[L, block_size, ...]`` — the wire-format contract an inter-replica
        migration codec (serve/router.py) validates before any byte lands
        on the destination. Two replicas serving the same model/config have
        identical specs; a mismatch means the ticket is not importable."""
        return {k: ((leaf.shape[0], self.block_size)
                    + tuple(leaf.shape[3:]), str(leaf.dtype))
                for k, leaf in self.cache.items()}

    @property
    def token_nbytes(self) -> float:
        """Per-token KV bytes (offload-fraction denominator)."""
        return self.block_nbytes / self.block_size

    # ------------------------------------------------------------ extents
    def read_block(self, slot: int, blk: int,
                   cache: dict[str, Any] | None = None
                   ) -> dict[str, np.ndarray]:
        """Copy one block out. Pass a ``cache`` snapshot (leaf refs taken
        under the engine lock) to do the copy off the lock — jax arrays are
        immutable, so the snapshot stays consistent on a DMA thread."""
        lo, hi = self.token_range(blk)
        leaves = self.cache if cache is None else cache
        return {k: np.asarray(leaf[:, slot, lo:hi])
                for k, leaf in leaves.items()}

    def put(self, x) -> jax.Array:
        """Copy a host array onto the cache's device (an array already
        there is returned as it is)."""
        return jax.device_put(x, self.device)

    def write_block(self, slot: int, blk: int,
                    data: dict[str, np.ndarray]) -> None:
        lo, hi = self.token_range(blk)
        self.cache = {k: leaf.at[:, slot, lo:hi].set(self.put(data[k]))
                      for k, leaf in self.cache.items()}

    @property
    def staging_cap(self) -> int:
        """Device bytes of reloaded blocks the h2d stream may hold staged
        ahead of their ``restore_slot``: two slots' full extent."""
        return 2 * self.n_blocks * self.block_nbytes

    def restore_slot(self, slot: int, blocks: list[dict[str, Any]]) -> None:
        """Apply a resumed request's reloaded blocks 0..n-1 in ONE per-leaf
        scatter — block-wise application would copy every cache leaf once
        per block. A block comes either staged on the device by the h2d
        stream or as host arrays, which ``put`` copies here; ``put`` leaves
        a device block where it is, so both run the same device programs:
        a concatenate per leaf, then the scatter. Timed by the engine's
        ``serve.kv.restore_slot`` span: with every block staged, that is
        the two dispatches alone."""
        span = len(blocks) * self.block_size
        self.cache = {
            k: leaf.at[:, slot, :span].set(jnp.concatenate(
                [self.put(b[k]) for b in blocks], axis=1).astype(leaf.dtype))
            for k, leaf in self.cache.items()}

    def drop_slot(self, slot: int) -> None:
        self.cache = {k: leaf.at[:, slot].set(0)
                      for k, leaf in self.cache.items()}

    def scatter_prefill(self, slots: list[int], kv: dict[str, Any]) -> None:
        """Write prefill K/V (leaves [L, len(slots), S, ...]) into rows."""
        idx = self.put(np.asarray(slots, np.int32))
        S = next(iter(kv.values())).shape[2]
        self.cache = {k: leaf.at[:, idx, :S].set(kv[k].astype(leaf.dtype))
                      for k, leaf in self.cache.items()}

    def grow(self, new_bucket: int) -> None:
        pad = new_bucket - self.bucket
        if pad <= 0:
            return
        self.cache = {
            k: jnp.pad(leaf, [(0, 0), (0, pad)] + [(0, 0)] * (leaf.ndim - 2))
            for k, leaf in self.cache.items()}
        self.bucket = new_bucket
