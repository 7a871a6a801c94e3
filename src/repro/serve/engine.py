"""Continuous-batching serving engine with block-paged KV-cache CPU offload.

The paper's §9 limitation — TURNIP executes *static* graphs, so recursive
generation must run over pre-compiled plans — becomes the design here
rather than a caveat:

* **Request queue → bucketed static batches.** Requests are submitted to a
  queue and admitted into fixed batch *slots*; decode is jitted once per
  batch bucket, so every step executes the same compiled program over
  ``[bucket, 1]`` tokens with per-row cache positions. Rows at different
  depths share one plan (continuous batching); slots without a live request
  are *inert* — ``decode_step``'s ``active`` mask keeps them from writing
  to the cache, and their logits are never sampled.
* **Real batched prefill.** A prompt enters the cache through ONE forward
  (:meth:`~repro.models.lm.LM.prefill`) instead of token-by-token teacher
  forcing; the request's first token samples from the prefill logits.
* **MEMGRAPH memory discipline.** The KV cache is a
  :class:`~repro.serve.kv_cache.PagedKVCache`: block-granular static
  extents over a preallocated cache. Cold blocks are *mirrored* to the
  TURNIP :class:`~repro.core.runtime.HostStore` on a dedicated d2h stream,
  and swapped-out requests are restored on an h2d stream — transfers run on
  their own engine classes (:data:`~repro.core.dispatch.D2H` /
  :data:`~repro.core.dispatch.H2D`) and overlap under decode, so steps
  never block on a transfer (paper §5). The main loop owns all cache
  mutation; DMA threads only copy blocks between tiers (the h2d stream
  stages a resume's blocks on the device) and post completion events.
* **Nondeterministic reload order.** Which pending transfer a DMA stream
  services next is a :class:`~repro.core.dispatch.DispatchPolicy` decision:
  ``fixed`` replays block-creation order (the compile-time-order ablation —
  blocks of concurrently decoding requests interleave, so no request
  resumes until nearly all transfers finish: §8's head-of-line pathology),
  while ``critical-path`` completes the request that can resume soonest.
* **A bounded host tier with disk spill.** ``host_kv_bytes`` caps the
  host-RAM KV mirror (online serving hits the CPU-RAM ceiling first —
  NEO, PAPERS.md): past it, least-recently-used mirrored blocks spill to
  a file-backed :class:`~repro.core.stores.TieredStore` disk tier on a
  dedicated disk stream (:data:`~repro.core.dispatch.DISK` — spills and
  loads never occupy a DMA lane), and a swapped request's disk-resident
  blocks resume through pipelined two-hop ``disk→host→device`` chains,
  with ``critical-path`` issuing the slow disk loads ahead of background
  spills. Tier placement changes timing only — never tokens.
* **Predictive cross-tier prefetch (NEO-style).** The scheduler knows
  which swapped request resumes next — waiting for its admission to
  discover its blocks live on disk is exactly the reactive stall the
  compiler-side PrefetchPlan removes from MEMGRAPH plans (DESIGN.md §11).
  While decode runs, the engine stages the next-scheduled swapped
  requests' disk-resident blocks back into host RAM on the disk stream
  (``prefetch_swapped``), bounded by the host budget's free headroom so a
  prefetch can never trigger spill thrash; a resume then needs only the
  h2d hop. Prefetch is opportunistic — a block that misses the window
  simply takes the two-hop chain as before.

* **Arbitrated shared host pool (DESIGN.md §12).** Pass
  ``Engine(pool=HostPool(...))`` and the KV mirror lives in a pool-level
  budget shared with other consumers (a runtime's MEMGRAPH offloads):
  the engine holds ``kv`` and ``prefetch`` leases and *reserves* every
  host-bound block against its lease before the transfer is submitted —
  a refusal defers the transfer (mirrors skip, preemption waits,
  admissions re-queue) and the recorded pressure drives the engine's own
  LRU spills on the disk stream. Revocations (another consumer
  outranking us) arrive as a flag; the next scheduler pass drains the
  overage. Arbitration changes timing only — tokens never move.

Sampling uses a per-``(seed, request, position)`` key schedule, so a
request's tokens are independent of batch composition, padding, offload,
and reload order — :func:`naive_generate` is the unbatched oracle any
engine configuration must match.
"""
from __future__ import annotations

import contextlib
import dataclasses
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import lockcheck
from ..core.dispatch import D2H, DISK, H2D, DispatchPolicy
from ..core.executor import select_best
from ..core.liveness import (LeaseSpec, LivenessCertificate,
                             LivenessModelError, PoolConfig,
                             certify_progress)
from ..core.memgraph import MemGraph
from ..core.stores import HostStore, TieredStore
from .kv_cache import PagedKVCache
from .spans import Span

__all__ = ["ServeConfig", "Engine", "Request", "ServeStats", "LOOP_PHASES",
           "ReloadPolicy", "RELOAD_POLICY_NAMES", "get_reload_policy",
           "ReplicaKilled", "MigrationRefused", "MigrationTicket",
           "naive_generate"]

# request lifecycle
QUEUED, RUNNING, SWAPPING, SWAPPED, RELOADING, DONE = (
    "queued", "running", "swapping-out", "swapped", "reloading", "done")


class ReplicaKilled(RuntimeError):
    """The replica's run loop was hard-killed (fault-injection seam or
    ``hard_kill()``): device state is gone, but the host/disk tiers — owned
    by the host process, not the dead worker — survive for draining."""


class MigrationRefused(RuntimeError):
    """All-or-nothing import refused: the destination could not reserve the
    whole KV set against its lease (or the ticket failed validation).
    Nothing landed — the caller falls back to cold re-prefill."""


@dataclasses.dataclass
class MigrationTicket:
    """A request checkpointed at its last emitted token, portable between
    replicas. ``blocks`` carries the KV payloads of a *warm* ticket (one
    ``{leaf: ndarray}`` dict per block, exactly ``read_block``'s layout);
    ``None`` means cold — device state died with the source replica and the
    destination must re-prefill ``prompt + out`` (token-exact because the
    sampling key schedule folds only (seed, rid, position), all three of
    which the ticket preserves)."""

    rid: int
    prompt: list[int]
    out: list[int]
    max_new: int
    pos: int
    last: int
    block_size: int
    t_submit: float = 0.0
    t_first: float = 0.0
    blocks: "list[dict] | None" = None

    @property
    def warm(self) -> bool:
        return self.blocks is not None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 512
    # the cache grows to the smallest bucket covering demand and stays
    # there (no shrink/compaction): after a burst, decode keeps running
    # the largest-bucket plan with inert rows masked
    batch_buckets: tuple[int, ...] = (1, 4, 8)
    temperature: float = 0.0          # 0 = greedy
    block_size: int = 32              # tokens per KV block (offload extent)
    # ---- offload / swapping ------------------------------------------
    offload: bool = False             # mirror cold KV blocks to host RAM
    hot_window: int = 32              # trailing tokens that never offload
    offload_fraction: float = 1.0     # cap: mirrored fraction of a request
    preempt_every: int = 0            # decode quantum before a running
    #                                   request may be swapped out for a
    #                                   waiter (0 = never preempt)
    reload_policy: str = "critical-path"   # fixed|random|critical-path
    # ---- disk tier (second threshold of the hierarchy) ----------------
    # host_kv_bytes bounds the host-RAM KV mirror: once occupancy passes
    # it, the engine spills least-recently-used mirrored blocks to a
    # file-backed disk tier on a dedicated disk stream (NEO's CPU-RAM
    # ceiling made runnable). Reloading a disk-resident block is a
    # pipelined two-hop disk→host→device chain. None = unbounded host.
    host_kv_bytes: int | None = None
    disk_bw: float = 2.4e9
    # NEO-style predictive prefetch: stage the next-scheduled swapped
    # requests' disk-resident blocks back into host RAM ahead of their
    # admission, within the host budget's free headroom (timing only —
    # tokens never depend on it)
    prefetch_swapped: bool = True
    # simulated wire time, slept on the DMA thread ON TOP of the real
    # device<->host copies (like TurnipRuntime's `latency` injection); set
    # dma_latency=0 and infinite bandwidths to sleep nothing
    h2d_bw: float = 12e9
    d2h_bw: float = 12e9
    dma_latency: float = 10e-6
    # fused DMA submissions (DESIGN.md §15, serving face): a stream that
    # wakes with several transfers pending issues them as one batched
    # submission — one enqueue + one fixed-latency completion wait for the
    # whole run instead of per transfer. Timing-only: tokens are
    # byte-identical either way (service order = pop order).
    fuse_dma: bool = False
    max_fuse_dma: int = 8
    seed: int = 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    out: list[int] = dataclasses.field(default_factory=list)
    state: str = QUEUED
    slot: int = -1
    pos: int = 0                      # tokens resident in the cache
    last: int = 0                     # last sampled token (next decode feed)
    quantum: int = 0                  # decode steps since (re)admission
    mirrored: set[int] = dataclasses.field(default_factory=set)
    inflight: set[int] = dataclasses.field(default_factory=set)
    pending_reload: set[int] = dataclasses.field(default_factory=set)
    reload_data: dict[int, dict] = dataclasses.field(default_factory=dict)
    # TTFT stamps (router-level p99 accounting): submission and first-token
    # instants in time.monotonic() seconds. Carried across migrations in
    # the ticket, so a resumed request keeps its original latency history.
    t_submit: float = 0.0
    t_first: float = 0.0
    # lifecycle stamps (time.monotonic()): preempted, slot granted back
    t_swap: float = 0.0
    t_grant: float = 0.0


@dataclasses.dataclass
class ServeStats:
    tokens: int = 0                   # all emitted (incl. prefill-sampled)
    decode_tokens: int = 0            # emitted by decode steps only
    prefill_tokens: int = 0
    decode_steps: int = 0
    decode_time: float = 0.0
    prefill_time: float = 0.0
    stall_time: float = 0.0           # wall time with no resident row to step
    swaps: int = 0
    revocations: int = 0              # pool grant shrinkages signalled to us
    lease_deferrals: int = 0          # transfers deferred by a refused
    #                                   reservation (shared-pool mode)
    offload_bytes: int = 0
    reload_bytes: int = 0
    disk_spill_bytes: int = 0         # host→disk tier traffic
    disk_load_bytes: int = 0          # disk→host tier traffic
    prefetch_bytes: int = 0           # disk→host bytes staged *ahead* of a
    #                                   resume (subset of disk_load_bytes)
    fused_dma_batches: int = 0        # multi-transfer submissions issued
    #                                   (ServeConfig.fuse_dma)
    kv_bytes_written: int = 0
    migrations_in: int = 0            # warm tickets imported (router fleet)
    migrations_out: int = 0           # warm tickets exported off this
    #                                   replica (drain + live rebalance)
    # ---- run-loop phases (LOOP_PHASES): wall seconds of the loop
    # thread, each the counter of one serve.* span (spans.py). Every
    # instant of run() lies in exactly one; decode_time, prefill_time
    # and stall_time (above) are three of them.
    hook_time: float = 0.0            # on_step and the pause wait
    lock_wait_time: float = 0.0       # acquiring the engine lock
    events_time: float = 0.0          # completion events, kill checks
    restore_time: float = 0.0         # restore_slot: a resume's on-device
    #                                   concatenate and scatter dispatch,
    #                                   and the h2d copy of unstaged blocks
    restores: int = 0
    drop_time: float = 0.0            # drop_slot of a swapped-out slot
    admit_time: float = 0.0           # admission planning, prefill inputs
    scatter_time: float = 0.0         # prefill K/V scatter, first tokens
    schedule_time: float = 0.0        # offload/spill/prefetch/preempt
    #                                   passes and the decode inputs
    emit_time: float = 0.0            # new cache handed over, sampling
    # ---- DMA streams: wall seconds and bytes of each stream's thread
    d2h_copy_time: float = 0.0        # read_block (device -> host copy)
    d2h_copy_bytes: int = 0
    d2h_store_time: float = 0.0       # put_offload under the engine lock
    h2d_copy_time: float = 0.0        # device_put of a reloaded block
    h2d_copy_bytes: int = 0
    h2d_staged_blocks: int = 0        # blocks restore_slot found staged on
    #                                   the device by the h2d stream ...
    h2d_unstaged_blocks: int = 0      # ... and blocks it copied itself
    #                                   (the stream was at staging_cap)
    disk_io_time: float = 0.0         # spill, load and prefetch file I/O
    disk_compact_time: float = 0.0    # rewrites of the disk tier's log
    d2h_wire_time: float = 0.0        # simulated wire sleeps, per stream
    h2d_wire_time: float = 0.0
    disk_wire_time: float = 0.0
    # ---- request lifecycle (time.monotonic() stamps)
    queue_time: float = 0.0           # submission -> first admission
    admissions: int = 0
    swap_stall_time: float = 0.0      # preemption -> running again
    resumes: int = 0
    swapped_time: float = 0.0         # preemption -> slot granted
    reloading_time: float = 0.0       # slot granted -> running again

    @property
    def offloaded_fraction(self) -> float:
        return self.offload_bytes / max(self.kv_bytes_written, 1)

    @property
    def loop_time(self) -> float:
        """Wall seconds of the run loop: the sum of its phases."""
        return sum(getattr(self, k) for k in LOOP_PHASES)


LOOP_PHASES = ("hook_time", "lock_wait_time", "events_time", "restore_time",
               "drop_time", "admit_time", "prefill_time", "scatter_time",
               "schedule_time", "decode_time", "emit_time", "stall_time")


# --------------------------------------------------------------------------
# DMA transfers + reload-order policies
# --------------------------------------------------------------------------
@dataclasses.dataclass
class _Transfer:
    kind: str                         # dispatch.D2H | dispatch.H2D | dispatch.DISK
    rid: int
    blk: int
    seq: int                          # block-creation order (see below)
    nbytes: int
    disk_op: str = ""                 # DISK: "spill" | "load" | "prefetch"


class ReloadPolicy(DispatchPolicy):
    """DispatchPolicy over pending serve transfers.

    Unlike the MEMGRAPH policies (static priorities per graph), urgency
    here is *dynamic*: it depends on which requests are currently blocked,
    so ``priority`` is evaluated at pop time under the engine lock."""

    name = "serve-base"

    def prepare(self, engine) -> None:              # type: ignore[override]
        self.engine = engine

    def priority(self, tr: _Transfer) -> float:     # type: ignore[override]
        raise NotImplementedError

    def pick(self, pending: list[_Transfer]) -> _Transfer:
        # the executor kernel's dispatch primitive (DESIGN.md §17): a
        # serve DMA stream's choice among pending transfers is the same
        # "policy minimum of the simultaneously-ready set" as a MEMGRAPH
        # seam's choice among ready vertices
        best = select_best(pending,
                           lambda tr: (self.priority(tr), tr.seq))
        return pending.pop(best)


class FixedReloadPolicy(ReloadPolicy):
    """Strict block-creation order — the predetermined schedule.

    Block seq numbers are assigned as blocks turn cold, which happens in
    lockstep across concurrently decoding slots, so two swapped requests'
    reloads interleave: neither resumes until nearly every transfer is done
    — the head-of-line pathology of the paper's fixed mode (§8)."""

    name = "fixed"

    def priority(self, tr: _Transfer) -> float:
        return float(tr.seq)


class RandomReloadPolicy(ReloadPolicy):
    """Seeded uniform-random priority (the any-order-must-work stance)."""

    name = "random"

    def __init__(self, seed: int | None = None) -> None:
        self.seed = random.randrange(2**31) if seed is None else seed

    def priority(self, tr: _Transfer) -> float:
        # integer-only mixing: builtin hash() of strings is salted per
        # process (PYTHONHASHSEED), which would defeat the seed
        ident = (tr.rid * 2654435761 + tr.blk * 40503 + (tr.kind == H2D)
                 + (tr.kind == DISK) * 7919 + (tr.disk_op == "spill") * 104729)
        return random.Random(
            (self.seed * 1000003 + 0x9E3779B9) ^ ident).random()


class CriticalPathReloadPolicy(ReloadPolicy):
    """Complete the request that can resume soonest: fewest outstanding
    transfers first, most remaining decode work as tie-break — the serving
    analogue of longest-path-first list scheduling.

    On the disk stream, loads (a blocked request's two-hop reload — the
    long pole) always outrank spills (background tier maintenance), so
    disk-resident blocks of resuming requests are issued earliest."""

    name = "critical-path"

    def priority(self, tr: _Transfer) -> float:
        req = self.engine.reqs.get(tr.rid)
        if req is None:                    # released mid-flight: drain first
            return -1e12
        if tr.disk_op == "spill":
            return 1e12                    # never ahead of a pending load
        if tr.disk_op == "prefetch":
            # opportunistic staging: behind any blocked request's load,
            # ahead of background spills
            return 1e9
        remaining_work = req.max_new - len(req.out)
        return len(req.inflight) * 1e6 - remaining_work


RELOAD_POLICY_NAMES = ("fixed", "random", "critical-path")


def get_reload_policy(policy: str | ReloadPolicy | None, *,
                      seed: int | None = None) -> ReloadPolicy:
    if isinstance(policy, ReloadPolicy):
        return policy
    if policy is None or policy == "critical-path":
        return CriticalPathReloadPolicy()
    if policy == "fixed":
        return FixedReloadPolicy()
    if policy == "random":
        return RandomReloadPolicy(seed)
    raise ValueError(f"unknown reload policy {policy!r}; "
                     f"expected one of {RELOAD_POLICY_NAMES}")


class _DmaStream(threading.Thread):
    """A dedicated transfer engine for one DMA direction.

    Pops the best-ranked pending transfer (policy choice = the runtime's
    nondeterministic dispatch), sleeps the simulated wire time *off* the
    engine lock so transfers overlap under decode (the ``serve.dma.wire``
    span, into ``stats.<kind>_wire_time``), then runs the service
    callback (a short memcpy / completion event under the lock)."""

    def __init__(self, kind: str, bw: float, latency: float,
                 policy: ReloadPolicy, service, lock: threading.Lock, *,
                 stats: ServeStats, fuse: bool = False, max_fuse: int = 8,
                 on_batch=None) -> None:
        super().__init__(name=f"serve-dma-{kind}")
        self.kind = kind
        self.stats = stats
        self.bw = bw
        self.latency = latency
        self.policy = policy
        self.service = service
        self.pending: list[_Transfer] = []
        self.cond = threading.Condition(lock)
        self.stopped = False
        self.error: BaseException | None = None
        # fused submissions (ServeConfig.fuse_dma): drain up to max_fuse
        # pending transfers per wake-up into one batched submission — one
        # enqueue + one fixed-latency completion wait for the run. Wire
        # time still charges every byte; service order = pop order, so
        # token streams are byte-identical with fusion on or off.
        self.fuse = fuse
        self.max_fuse = max_fuse
        self.on_batch = on_batch      # called (lock held) per fused batch

    def submit(self, tr: _Transfer) -> None:
        """Engine lock held."""
        self.pending.append(tr)
        self.cond.notify_all()

    def shutdown(self) -> None:
        """Engine lock held. Unserviced transfers are abandoned."""
        self.stopped = True
        self.pending.clear()
        self.cond.notify_all()

    def run(self) -> None:
        try:
            while True:
                with self.cond:
                    while not self.pending and not self.stopped:
                        self.cond.wait()
                    if self.stopped:
                        return
                    batch = [self.policy.pick(self.pending)]
                    while (self.fuse and self.pending
                           and len(batch) < self.max_fuse):
                        batch.append(self.policy.pick(self.pending))
                    if len(batch) > 1 and self.on_batch is not None:
                        self.on_batch(len(batch))
                # one submission for the run: a single fixed launch
                # latency plus every member's wire bytes
                nbytes = sum(t.nbytes for t in batch)
                wire = self.latency + nbytes / self.bw
                if wire > 0:
                    with Span(self.stats, f"{self.kind}_wire_time",
                              "serve.dma.wire", rid=batch[0].rid,
                              blk=batch[0].blk, nbytes=nbytes,
                              transfers=len(batch)):
                        time.sleep(wire)
                for tr in batch:
                    self.service(tr)
        except BaseException as e:       # surface in the engine loop — a
            with self.cond:              # silently dead stream would wedge
                self.error = e           # every waiter forever
                self.stopped = True
                self.cond.notify_all()


# --------------------------------------------------------------------------
# sampling — shared by the engine and the unbatched oracle
# --------------------------------------------------------------------------
def _sample_token(row_logits: np.ndarray, *, seed: int, rid: int, pos: int,
                  temperature: float, vocab_size: int) -> int:
    """Sample the token at absolute position ``pos`` of request ``rid``.

    The key schedule folds (seed, rid, pos), so a request's randomness is
    independent of batch composition and scheduling. Vocab padding is
    masked out (the padded tail of ``padded_vocab`` must be unsampleable).
    At temperature > 0 this is an eager per-token jax call — a deliberate
    correctness-first tradeoff (the engine and the oracle share this exact
    code path); a throughput-focused engine would vmap the fold_in +
    categorical over rows inside the jitted step."""
    row = row_logits[:vocab_size].astype(np.float32)
    if temperature <= 0:
        return int(np.argmax(row))
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), rid), pos)
    return int(jax.random.categorical(key, jnp.asarray(row) / temperature))


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------
class Engine:
    """Continuous-batching decode engine over a block-paged KV cache."""

    def __init__(self, model, params, cfg: ServeConfig = ServeConfig(), *,
                 host: HostStore | None = None, pool=None,
                 name: str = "serve", device=None):
        """``device``: the accelerator this replica owns (default: the
        first device JAX sees). Params, the KV cache and every host→device
        copy live there; a router hands each replica its own.

        ``host``: pass a runtime's :class:`HostStore` (or
        :class:`TieredStore`) to share one pinned host pool (and its
        traffic counters) with it; by default the engine owns a private
        arena — tiered (host + disk) when ``cfg.host_kv_bytes`` bounds the
        KV mirror, plain otherwise.

        ``pool``: a :class:`~repro.core.pool.HostPool` (DESIGN.md §12).
        The engine takes two leases — ``kv`` (resident KV mirror bytes,
        high priority: these blocks resume blocked requests) and
        ``prefetch`` (opportunistic predictive staging, lowest priority)
        — and *reserves* every host-bound block against its lease before
        the transfer is submitted, so KV bytes can never land past the
        arbitrated share: a refused reservation defers the transfer and
        the recorded pressure drives the engine's own LRU spills on its
        disk stream. Under a pool the budget is the lease's arbitrated
        *grant*, not ``cfg.host_kv_bytes`` — but a nonzero
        ``host_kv_bytes`` carries its sizing intent into the arbiter as
        the kv lease's inviolable floor (``min_bytes``; lease creation
        raises if the floors jointly exceed the pool). The engine keeps
        its own store; the pool — not a shared store object — is the
        sharing surface, so don't pass a lease-attached store as ``host``
        (its occupancy accounting would double-count the engine's
        reservations)."""
        if model.cfg.family not in ("dense", "moe"):
            raise ValueError("serving engine requires a KV-cache family "
                             f"(dense/moe), got {model.cfg.family!r}")
        if cfg.max_len % cfg.block_size:
            raise ValueError("max_len must be a multiple of block_size")
        if pool is not None and getattr(host, "lease", None) is not None:
            raise ValueError("shared store already lease-attached: pool "
                             "arbitration would double-count its bytes")
        self.model = model
        self.device = device if device is not None else jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.cfg = cfg
        self.name = name            # replica identity (router + diagnostics)
        self._pool = pool
        if host is not None:
            self.host = host
            self._owns_host = False
        elif pool is not None:
            # pooled: budget enforcement is reservation-driven at the
            # engine level (charge-before-submit), so the store itself is
            # unbounded and spills stay engine-driven on the disk stream
            self.host = TieredStore({}, auto_spill=False)
            self._owns_host = True
        elif cfg.host_kv_bytes is not None:
            # spills are engine-driven (auto_spill off) so the disk I/O
            # cost lands on the disk stream's timeline, not inside put
            self.host = TieredStore({}, host_capacity=cfg.host_kv_bytes,
                                    auto_spill=False)
            self._owns_host = True
        else:
            self.host = HostStore({})
            self._owns_host = True
        self._tiered = isinstance(self.host, TieredStore)
        if self._tiered and self._owns_host:
            # the disk stream compacts the disk tier's log (_compact_disk):
            # inline, a drop under the engine lock would wait for a rewrite
            # of the whole live tier, seconds at full size
            self.host.disk.compact_inline = False
        # per-key reservation ledger: key -> (lease, charged bytes). A key
        # appears here from the moment its host-bound transfer is charged
        # until its host copy is spilled/popped — the release always uses
        # the exact bytes that were charged.
        self._charged: dict[tuple[int, int], tuple] = {}
        # revocation pressure signal (set from arbitrary threads via the
        # pool's callback — a leaf lock, never the engine lock, so a
        # same-thread revocation during our own charge cannot deadlock)
        self._revoke_lock = lockcheck.make_lock("ServeEngine.revoke")
        self._revoked_pending = 0
        if pool is not None:
            # drains_via=(): both leases' revocation drains (the disk-
            # stream spill path) only *release* bytes, never charge
            # another lease — the declaration the liveness model checks
            # at runtime (assumption A2, DESIGN.md §14)
            self._kv_lease = pool.lease(
                "kv", min_bytes=cfg.host_kv_bytes or 0, weight=2.0,
                priority=2, on_revoke=self._on_revoke, drains_via=())
            self._pf_lease = pool.lease(
                "prefetch", weight=1.0, priority=0,
                on_revoke=self._on_revoke, drains_via=())
            # statically certify the engine's pool configuration live
            # (DESIGN.md §14): structural passes only — floors jointly
            # feasible, no revocation-drain cycles, no waits-for cycle in
            # the lease/stream resource-allocation graph. When this holds,
            # the no-progress detector below is provably unreachable, so
            # its firing is escalated to certifier unsoundness.
            self._liveness_certificate: LivenessCertificate | None = \
                certify_progress(MemGraph(), self.pool_model())
            self._certified_live = self._liveness_certificate.ok
        else:
            self._kv_lease = self._pf_lease = None
            self._liveness_certificate = None
            self._certified_live = False
        self.reqs: dict[int, Request] = {}
        self._live: set[int] = set()                # rids not yet DONE
        self.stats = ServeStats()
        self.kv: PagedKVCache | None = None
        # one jit wrapper per program, compiled per argument signature
        # (batch bucket, prompt pad length) by _run_program
        self._step = jax.jit(model.decode_step)
        self._prefill = jax.jit(model.prefill)
        self._programs: dict = {}
        self._next_rid = 0
        self._queue: list[int] = []                 # QUEUED rids, FIFO
        self._swapped: list[int] = []               # SWAPPED rids, FIFO
        self._slots: list[int | None] = []
        self._events: list[tuple] = []              # completions to apply
        self._block_seq: dict[tuple[int, int], int] = {}
        self._seq_counter = 0
        self._seed = cfg.seed
        self._lock = lockcheck.make_lock("ServeEngine")
        self._wake = threading.Condition(self._lock)
        self._d2h: _DmaStream | None = None
        self._h2d: _DmaStream | None = None
        self._disk: _DmaStream | None = None
        self._spill_inflight: set[tuple[int, int]] = set()
        self._prefetch_inflight: set[tuple[int, int]] = set()
        # reloaded blocks the h2d stream copied onto the device, not yet
        # applied by restore_slot; bounded by PagedKVCache.staging_cap
        self._staged: set[tuple[int, int]] = set()
        self._idle_spins = 0            # consecutive no-progress stalls
        self._idle_pool_state = None    # last observed (pool used, grant)
        # ---- fleet / fault-injection seams (serve/router.py) ------------
        # on_step: called once per run-loop iteration OFF the engine lock —
        # the router wires it to Heartbeat.beat(replica), so a wedged or
        # paused loop stops beating and the supervisor notices.
        self.on_step = None
        # on_compile: called on the run-loop thread just before a model
        # program compiles for a new shape — seconds of silence at full
        # size, which the router announces to the heartbeat as a grace
        # period instead of mistaking it for a wedge.
        self.on_compile = None
        # on_token: called under the engine lock with each request whose
        # token was just appended and the logit row it was sampled from —
        # how a check compares the engine's logits with the oracle's.
        self.on_token = None
        # hard-kill seams: `hard_kill()` (async, from any thread) or
        # `fault_after_steps` (deterministic: raise once this many decode
        # steps have run — the chaos harness's seeded kill instants). Both
        # raise ReplicaKilled out of run(); the finally block still joins
        # every DMA stream, so a killed replica leaks no threads.
        self._killed = False
        self.fault_after_steps: int | None = None
        # stall seam: `pause()` blocks the run loop (heartbeats stop, the
        # loop thread stays alive) until `resume()` — the missed-heartbeat
        # path that is NOT a crash.
        self._pause_evt = threading.Event()
        self._pause_evt.set()
        # the run-loop phase that is open, as (span name, perf_counter()
        # at its start); None outside run(). A plain attribute, written by
        # the loop thread only: the router reads it when it drains a
        # silent replica.
        self.phase: tuple[str, float] | None = None

    # ---------------------------------------------- pool lease bookkeeping
    def pool_model(self) -> PoolConfig:
        """The engine's pool population as the static liveness model sees
        it (DESIGN.md §14): every lease a reserving consumer with its
        declared drain routes, co-tenants included as they stand."""
        specs = tuple(LeaseSpec(
            name=l.name, min_bytes=l.min_bytes, weight=l.weight,
            priority=l.priority, discipline="reserving",
            drains_via=tuple(getattr(l, "drains_via", ())))
            for l in self._pool.leases())
        return PoolConfig(capacity=self._pool.capacity, leases=specs,
                          policy=getattr(self._pool.policy, "name",
                                         "static"))

    def _waits_for_locked(self) -> dict:
        """The live waits-for graph, dumped when the no-progress detector
        fires: who holds what, who is blocked on what. Diagnostic only —
        the detector itself is demoted to a certifier-soundness check for
        certified configurations. Leads with the replica name: under a
        router N engines share one traceback consumer, and a wedge report
        that can't say *which* replica wedged is useless."""
        if self._pool is not None:
            leases = {
                l.name: {"grant": l.grant, "used": l.used,
                         "pressure": l.pressure, "overage": l.overage,
                         "refusals": l.refusals}
                for l in self._pool.leases()}
            pool = {"capacity": self._pool.capacity,
                    "used_bytes": self._pool.used_bytes}
        else:
            leases = {}
            pool = None
        with self._revoke_lock:
            revoked = self._revoked_pending
        return {
            "replica": self.name,
            "pool": pool,
            "leases": leases,
            "revoked_pending": revoked,
            "queued": list(self._queue),
            "swapped": list(self._swapped),
            "spill_inflight": sorted(self._spill_inflight),
            "prefetch_inflight": sorted(self._prefetch_inflight),
            "inflight": {r: sorted(self.reqs[r].inflight)
                         for r in self._live if self.reqs[r].inflight},
            "states": {r: self.reqs[r].state for r in self._live},
        }

    def _on_revoke(self, deficit: int) -> None:
        """Pool callback: another consumer's pressure shrank one of our
        grants below its charged bytes. Must stay cheap and lock-light —
        it can fire on any thread, including one already inside the
        engine lock — so it only records the pressure; the scheduler's
        next spill pass drains it through the disk stream (never a
        blocking inline write on the revoker's thread)."""
        with self._revoke_lock:
            self._revoked_pending += deficit

    def _charge_key_locked(self, key, lease, *, urgent: bool = True) -> bool:
        """Reserve one block's bytes on ``lease`` before submitting its
        host-bound transfer. True when the bytes may move (already charged,
        or the reservation fit); False defers the transfer."""
        if self._pool is None:
            return True
        if key in self._charged:
            return True
        n = self.kv.block_nbytes
        if not lease.try_charge(n, urgent=urgent):
            self.stats.lease_deferrals += 1
            return False
        self._charged[key] = (lease, n)
        return True

    def _release_key_locked(self, key) -> None:
        if self._pool is None:
            return
        entry = self._charged.pop(key, None)
        if entry is not None:
            entry[0].release(entry[1])

    def _transfer_key_locked(self, key, dst) -> None:
        """Move a charged key's reservation to ``dst`` (prefetch→kv when a
        staged block's request is admitted: the bytes are already host-
        resident, so the move is forced — dst drains any overage through
        its own spills)."""
        entry = self._charged.get(key)
        if entry is None or entry[0] is dst:
            return
        self._pool.transfer(entry[0], dst, entry[1])
        self._charged[key] = (dst, entry[1])

    # ------------------------------------------------------------- public
    def submit(self, prompt, max_new: int = 32, *,
               rid: int | None = None) -> int:
        """Enqueue a request; returns its id. Tokens emitted will be
        ``min(max_new, max_len - len(prompt) + 1)`` — the first token
        samples from the prefill logits, so a prompt that exactly fills the
        window still yields one token.

        ``rid`` pins the request id (fleet mode: the router allocates ids
        globally, because the sampling key schedule folds the rid — a
        request must keep its id across replicas for its tokens to be
        identical wherever it lands)."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError("max_new must be >= 1 (the first token always "
                             "samples from the prefill logits)")
        if len(prompt) > self.cfg.max_len:
            raise ValueError(f"prompt of {len(prompt)} tokens exceeds "
                             f"max_len={self.cfg.max_len}")
        with self._lock:        # online use submits while run() is draining
            if rid is None:
                rid = self._next_rid
            elif rid in self.reqs:
                raise ValueError(f"rid {rid} already present on replica "
                                 f"{self.name!r}")
            self._next_rid = max(self._next_rid, rid + 1)
            self.reqs[rid] = Request(rid, prompt, max_new,
                                     t_submit=time.monotonic())
            self._live.add(rid)
            self._queue.append(rid)
            self._wake.notify_all()     # a stalled run() picks it up now
        return rid

    def hard_kill(self) -> None:
        """Kill the replica from any thread: the run loop raises
        :class:`ReplicaKilled` at its next iteration (a stalled loop wakes
        within its 0.1 s wait tick). Device state is considered lost; the
        host/disk tiers stay intact for :meth:`drain_tickets`."""
        with self._lock:
            self._killed = True
            self._wake.notify_all()

    def pause(self) -> None:
        """Stall seam: block the run loop (and its heartbeats) without
        killing it — the silent-wedge failure mode a supervisor must
        distinguish from a crash. :meth:`resume` releases it."""
        self._pause_evt.clear()

    def resume(self) -> None:
        self._pause_evt.set()

    def close(self) -> None:
        """Release the engine-owned store's backing resources (the disk
        tier's temp directory and spilled blobs). Idempotent; a shared
        ``host`` store passed in by the caller is left untouched. A
        long-lived service should close the engine when retiring it."""
        if self._owns_host:
            self.host.close()
        if self._pool is not None:
            # retire our leases: their shares return to the pool (any
            # still-charged bytes are dropped with the store)
            self._kv_lease.close()
            self._pf_lease.close()
            self._charged.clear()

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def release(self, rid: int) -> None:
        """Drop a finished request's record. Finished requests otherwise
        stay in ``reqs`` so callers can read their tokens; a long-lived
        online engine should release them once consumed."""
        with self._lock:
            req = self.reqs.get(rid)
            if req is not None and req.state != DONE:
                raise ValueError(f"request {rid} is {req.state}, not done")
            self.reqs.pop(rid, None)

    # ------------------------------------- KV migration (DESIGN.md §16)
    def _warm_payload_locked(self, req: Request) -> "list[dict] | None":
        """Collect a SWAPPED request's complete block set from the host/
        disk tiers (``peek_offload``: no restaging, no traffic counted).
        ``None`` unless *every* block is present and quiescent — a warm
        ticket is all blocks or nothing, the export face of all-or-nothing
        admission."""
        if req.state != SWAPPED or req.inflight or req.pending_reload:
            return None
        blocks = []
        for blk in range(self.kv.n_token_blocks(req.pos)):
            data = self.host.peek_offload((req.rid, blk))
            if data is None:
                return None
            blocks.append({k: np.asarray(v) for k, v in data.items()})
        return blocks

    def _ticket_locked(self, req: Request,
                       blocks: "list[dict] | None") -> MigrationTicket:
        return MigrationTicket(
            rid=req.rid, prompt=list(req.prompt), out=list(req.out),
            max_new=req.max_new, pos=req.pos, last=req.last,
            block_size=self.cfg.block_size,
            t_submit=req.t_submit, t_first=req.t_first, blocks=blocks)

    def drain_tickets(self) -> list[MigrationTicket]:
        """Checkpoint every live request at its last emitted token for
        migration off this replica — the post-kill drain. SWAPPED requests
        whose full block set survives on the host/disk tiers (owned by the
        host process, which outlives the dead worker) become *warm*
        tickets; everything else lost its device state with the worker and
        goes *cold* (the destination re-prefills ``prompt + out``).
        Read-only on the source: the caller retires it with ``close()``."""
        tickets = []
        with self._lock:
            for rid in sorted(self._live):
                req = self.reqs[rid]
                blocks = (self._warm_payload_locked(req)
                          if self.kv is not None else None)
                tickets.append(self._ticket_locked(req, blocks))
        return tickets

    def export_one_swapped(self) -> MigrationTicket | None:
        """Live rebalance: detach the *tail* of the swapped FIFO (the
        request that would wait longest for a local slot) as a warm
        ticket, releasing its local bytes, lease charges, and seq entries.
        ``None`` when no swapped request has a complete, quiescent block
        set (in-flight spills/prefetches defer the export — never race a
        stream for a block)."""
        with self._lock:
            if self.kv is None:
                return None
            for i in range(len(self._swapped) - 1, -1, -1):
                rid = self._swapped[i]
                req = self.reqs[rid]
                keys = [(rid, b)
                        for b in range(self.kv.n_token_blocks(req.pos))]
                if any(k in self._spill_inflight
                       or k in self._prefetch_inflight for k in keys):
                    continue
                blocks = self._warm_payload_locked(req)
                if blocks is None:
                    continue
                ticket = self._ticket_locked(req, blocks)
                self._swapped.pop(i)
                self._live.discard(rid)
                self.reqs.pop(rid)
                for k in keys:
                    self.host.pop_offload(k)
                    self._release_key_locked(k)
                    self._block_seq.pop(k, None)
                self.stats.migrations_out += 1
                self._wake.notify_all()   # run() re-checks its live set
                return ticket
        return None

    def load(self) -> tuple[int, int]:
        """Placement signals for a router: (live request count, resident +
        committed KV tokens). Cheap and exact under the engine lock."""
        with self._lock:
            return (len(self._live),
                    sum(max(self.reqs[r].pos, len(self.reqs[r].prompt))
                        for r in self._live))

    def import_migration(self, ticket: MigrationTicket) -> None:
        """Admit a warm ticket in SWAPPED state: validate every payload
        against this replica's :meth:`PagedKVCache.leaf_spec`, reserve the
        whole block set against the kv lease, then land the bytes in the
        host tier — **all or nothing**: a :class:`MigrationRefused` leaves
        no byte, charge, or request record behind, so the §12 pool
        invariants and the §14 liveness assumptions hold on the
        destination exactly as if the request had been swapped out
        locally. The request resumes through the ordinary swap-in path;
        the imported blocks are bit-identical to what ``restore_slot``
        would have reloaded on the source, so its continuation is
        token-exact."""
        if ticket.blocks is None:
            raise MigrationRefused(
                f"ticket {ticket.rid} is cold (no KV payload): resubmit "
                "prompt+out for re-prefill instead")
        if ticket.block_size != self.cfg.block_size:
            raise MigrationRefused(
                f"block_size mismatch: ticket has {ticket.block_size}, "
                f"replica {self.name!r} serves {self.cfg.block_size}")
        with self._lock:
            if ticket.rid in self.reqs:
                raise MigrationRefused(
                    f"rid {ticket.rid} already present on replica "
                    f"{self.name!r}")
            if self.kv is None:
                # a fresh replica has no cache yet; geometry (block bytes,
                # leaf spec) is needed before any payload can be validated
                bucket = self._bucket_for(1)
                self.kv = PagedKVCache(self.model, bucket, self.cfg.max_len,
                                       block_size=self.cfg.block_size,
                                       device=self.device)
                self._slots = [None] * bucket
            n_blocks = self.kv.n_token_blocks(ticket.pos)
            if len(ticket.blocks) != n_blocks:
                raise MigrationRefused(
                    f"ticket {ticket.rid} carries {len(ticket.blocks)} "
                    f"blocks for pos={ticket.pos} (want {n_blocks})")
            spec = self.kv.leaf_spec()
            for blk, data in enumerate(ticket.blocks):
                if set(data) != set(spec):
                    raise MigrationRefused(
                        f"ticket {ticket.rid} block {blk}: leaves "
                        f"{sorted(data)} != spec {sorted(spec)}")
                for leaf, (shape, dtype) in spec.items():
                    arr = data[leaf]
                    if tuple(arr.shape) != shape or str(arr.dtype) != dtype:
                        raise MigrationRefused(
                            f"ticket {ticket.rid} block {blk} leaf "
                            f"{leaf!r}: {arr.shape}/{arr.dtype} != "
                            f"{shape}/{dtype}")
            charged_now = []
            for blk in range(n_blocks):
                if self._charge_key_locked((ticket.rid, blk),
                                           self._kv_lease):
                    charged_now.append((ticket.rid, blk))
                else:
                    for key in charged_now:
                        self._release_key_locked(key)
                    raise MigrationRefused(
                        f"replica {self.name!r} cannot reserve "
                        f"{n_blocks} blocks for ticket {ticket.rid}: "
                        "kv lease refused the set")
            req = Request(ticket.rid, list(ticket.prompt), ticket.max_new,
                          out=list(ticket.out), state=SWAPPED,
                          pos=ticket.pos, last=ticket.last,
                          mirrored=set(range(n_blocks)),
                          t_submit=ticket.t_submit, t_first=ticket.t_first)
            for blk, data in enumerate(ticket.blocks):
                key = (ticket.rid, blk)
                self.host.put_offload(key, data)
                self._block_seq[key] = self._seq_counter
                self._seq_counter += 1
            self.reqs[ticket.rid] = req
            self._live.add(ticket.rid)
            self._swapped.append(ticket.rid)
            self._next_rid = max(self._next_rid, ticket.rid + 1)
            self.stats.migrations_in += 1
            self._wake.notify_all()

    def generate(self, prompts: list[list[int]], *, max_new: int = 32,
                 seed: int | None = None) -> list[list[int]]:
        """Submit ``prompts`` and run the queue to completion (the batch
        API the tests drive; online use is ``submit()`` + ``run()``)."""
        rids = [self.submit(p, max_new) for p in prompts]
        self.run(seed=seed)
        return [list(self.reqs[r].out) for r in rids]

    def run(self, *, seed: int | None = None) -> ServeStats:
        """Drain the queue: admit → prefill → decode, with offload/reload
        riding on DMA streams, until every submitted request is DONE.

        Returns once the live set is observed empty under the lock: a
        request submitted concurrently after that instant waits for the
        next ``run()`` — a long-lived online service keeps a run loop (or
        re-invokes ``run()`` after submitting).

        Each iteration is a sequence of loop phases, ``serve.*`` spans that
        never nest (``LOOP_PHASES``): hooks, lock waits, events (with
        ``restore_slot`` and ``drop_slot``), admission, prefill and its
        scatter, the scheduling passes, then decode and emit or a stall."""
        if seed is not None:
            self._seed = seed
        cfg = self.cfg
        pol = get_reload_policy(cfg.reload_policy, seed=self._seed)
        pol.prepare(self)
        def _on_batch(n: int) -> None:      # lock held (stream cond)
            self.stats.fused_dma_batches += 1

        fuse_kw = dict(stats=self.stats, fuse=cfg.fuse_dma,
                       max_fuse=cfg.max_fuse_dma, on_batch=_on_batch)
        self._d2h = _DmaStream(D2H, cfg.d2h_bw, cfg.dma_latency, pol,
                               self._service_d2h, self._lock, **fuse_kw)
        self._h2d = _DmaStream(H2D, cfg.h2d_bw, cfg.dma_latency, pol,
                               self._service_h2d, self._lock, **fuse_kw)
        streams = [self._d2h, self._h2d]
        if self._tiered:
            # the disk tier's own engine class: spills/loads never occupy
            # (or wait behind) the h2d/d2h DMA lanes
            self._disk = _DmaStream(DISK, cfg.disk_bw, cfg.dma_latency, pol,
                                    self._service_disk, self._lock,
                                    **fuse_kw)
            streams.append(self._disk)
        for stream in streams:
            stream.start()
        try:
            while True:
                with self._loop_span("serve.loop.hooks", "hook_time"):
                    if self.on_step is not None:
                        # off the lock: the heartbeat table is a leaf lock
                        # and the callback must never nest inside the
                        # engine lock
                        self.on_step(self)
                    self._pause_evt.wait()
                with self._loop_lock():
                    with self._loop_span("serve.loop.events", "events_time"):
                        if self._killed or (
                                self.fault_after_steps is not None
                                and self.stats.decode_steps
                                >= self.fault_after_steps):
                            raise ReplicaKilled(
                                f"replica {self.name!r} hard-killed after "
                                f"{self.stats.decode_steps} decode steps")
                        for stream in streams:
                            if stream.error is not None:
                                raise stream.error
                    self._apply_events_locked()
                    with self._loop_span("serve.loop.admit", "admit_time"):
                        admits = self._plan_admissions_locked()
                if admits:
                    self._prefill_admit(admits)
                with self._loop_lock():
                    with self._loop_span("serve.loop.schedule",
                                         "schedule_time"):
                        self._schedule_offload_locked()
                        self._schedule_spill_locked()
                        self._schedule_prefetch_locked()
                        self._schedule_preempt_locked()
                        active = [(s, r) for s, r in enumerate(self._slots)
                                  if r is not None
                                  and self.reqs[r].state == RUNNING]
                        if not self._live:  # atomic with submit()'s mutation
                            break
                        if active:
                            inputs = self._decode_inputs_locked(active)
                if active:
                    self._decode_once(active, inputs)
                    # the pre-step cache must not outlive its step: held
                    # here, it would double the cache on the device
                    # through the next iteration's paging and prefill
                    del inputs
                else:
                    self._stall_wait()
        finally:
            with self._lock:
                for stream in streams:
                    # an abandoned transfer never reaches its service hook:
                    # a finished request's reservation (an eager mirror
                    # still queued when its last token landed) would be
                    # held past the run forever
                    for tr in stream.pending:
                        req = self.reqs.get(tr.rid)
                        if req is None or req.state == DONE:
                            self._release_key_locked((tr.rid, tr.blk))
                    stream.shutdown()
                self._spill_inflight.clear()
                self._prefetch_inflight.clear()
            for stream in streams:
                stream.join()
            self.phase = None
        return self.stats

    def _loop_span(self, name: str, counter: str, **meta) -> Span:
        """A run-loop phase: a span whose name the engine keeps open in
        ``self.phase``. Loop phases never nest, so every instant of the
        loop lies in exactly one (``ServeStats.loop_time``)."""
        return Span(self.stats, counter, name, track=self, **meta)

    @contextlib.contextmanager
    def _loop_lock(self):
        """The engine lock, as the run loop takes it: the wait for it is
        the ``serve.loop.lock_wait`` phase."""
        with self._loop_span("serve.loop.lock_wait", "lock_wait_time"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    # -------------------------------------------------- DMA service hooks
    # (run on stream threads after the simulated wire time; they only copy
    # blocks and post events — the main loop owns cache mutation)
    def _service_d2h(self, tr: _Transfer) -> None:
        with self._lock:
            req = self.reqs.get(tr.rid)
            if req is None:                           # released mid-flight
                self._release_key_locked((tr.rid, tr.blk))
                self._wake.notify_all()
                return
            if req.state == DONE or req.slot < 0:
                req.inflight.discard(tr.blk)
                self._release_key_locked((tr.rid, tr.blk))
                self._wake.notify_all()
                return
            snapshot = self.kv.cache                  # immutable leaf refs
            slot = req.slot
        # the actual copy runs OFF the engine lock so it overlaps under
        # decode like a real copy engine; the slot cannot be reassigned
        # while this block is in flight (swap-out completes only once
        # `inflight` drains), so only completion can invalidate it
        meta = dict(rid=tr.rid, blk=tr.blk, nbytes=tr.nbytes)
        with Span(self.stats, "d2h_copy_time", "serve.d2h.copy", **meta):
            data = self.kv.read_block(slot, tr.blk, cache=snapshot)
        self.stats.d2h_copy_bytes += tr.nbytes
        with self._lock, Span(self.stats, "d2h_store_time",
                              "serve.d2h.store", **meta):
            req.inflight.discard(tr.blk)
            if req.state != DONE and req.slot == slot:
                self.host.put_offload((tr.rid, tr.blk), data)
                # counted here, not as a HostStore delta: a runtime sharing
                # the store must not have its traffic attributed to serving
                self.stats.offload_bytes += tr.nbytes
                req.mirrored.add(tr.blk)
                if req.state == SWAPPING and not req.inflight:
                    self._events.append(("swap-done", tr.rid))
            else:
                # payload dropped: the reservation made at submit time has
                # nothing backing it any more
                self._release_key_locked((tr.rid, tr.blk))
            self._wake.notify_all()

    def _service_h2d(self, tr: _Transfer) -> None:
        """Copy a reloaded block onto the cache's device, off the engine
        lock, so the copy overlaps decode; the run loop's ``restore_slot``
        then only concatenates and scatters. Staged bytes are bounded by
        ``PagedKVCache.staging_cap``: a block past it is posted as its
        host reference and ``restore_slot`` copies it. The bound never
        waits, so the stream has no new blocking edge."""
        key = (tr.rid, tr.blk)
        data = self.host.get_offload(key)
        with self._lock:
            stage = ((len(self._staged) + 1) * self.kv.block_nbytes
                     <= self.kv.staging_cap)
            if stage:
                self._staged.add(key)
        if stage:
            with Span(self.stats, "h2d_copy_time", "serve.h2d.copy",
                      rid=tr.rid, blk=tr.blk, nbytes=tr.nbytes):
                data = jax.block_until_ready(
                    {k: self.kv.put(v) for k, v in data.items()})
            self.stats.h2d_copy_bytes += tr.nbytes
        with self._lock:
            self.stats.reload_bytes += tr.nbytes
            req = self.reqs.get(tr.rid)
            if req is not None:
                req.inflight.discard(tr.blk)
                self._events.append(("reload", tr.rid, tr.blk, data))
            else:                                     # released mid-flight
                self._staged.discard(key)
            self._wake.notify_all()

    def _service_disk(self, tr: _Transfer) -> None:
        """Disk-stream service: ``spill`` moves a cold host block to the
        file tier, ``load`` stages a disk block back into host RAM and
        chains the h2d hop (the pipelined two-hop reload). Runs after the
        simulated disk wire time. Load file I/O happens off the engine
        lock (the store has its own lock) and overlaps under decode; the
        spill's small block write deliberately stays *under* the lock —
        admissions hold the same lock, so a swap-in can never claim a
        block mid-spill and drag the disk read onto the h2d lane via
        read-through. One block's write is cheap; the invariant is not."""
        self._compact_disk()
        key = (tr.rid, tr.blk)
        meta = dict(rid=tr.rid, blk=tr.blk, nbytes=tr.nbytes)
        if tr.disk_op == "prefetch":
            # predictive staging for a request still waiting in the swapped
            # queue: bring the blob host-side so its eventual resume is a
            # single h2d hop. The request may have finished or been
            # released mid-flight (blob popped) — then there is nothing to
            # stage and the prefetch is a benign no-op. The tier check is
            # exact here: all disk ops serialize on this one stream, so a
            # block the reactive path already staged (and counted) is seen
            # host-resident and not double-counted.
            try:
                with Span(self.stats, "disk_io_time", "serve.disk.prefetch",
                          **meta):
                    staged = self.host.tier_of(key) == "disk"
                    if staged:
                        self.host.load(key)
            except KeyError:
                staged = False
            with self._lock:
                self._prefetch_inflight.discard(key)
                req = self.reqs.get(tr.rid)
                if staged and (req is None or req.state == DONE
                               or key not in self._block_seq):
                    # the request retired while the blob was being read:
                    # _finish_locked already popped every copy, so the
                    # freshly staged bytes are an orphan nothing would
                    # ever release — undo the resurrection
                    self.host.pop_offload(key)
                    staged = False
                if (self._pool is not None
                        and self._charged.get(key, (None,))[0]
                        is self._pf_lease
                        and self.host.tier_of(key) != "host"):
                    # the reservation has no host bytes behind it (blob
                    # vanished mid-flight, or the staging was undone):
                    # give the prefetch share back
                    self._release_key_locked(key)
                if staged:
                    self.stats.disk_load_bytes += tr.nbytes
                    self.stats.prefetch_bytes += tr.nbytes
                if req is not None and tr.blk in req.pending_reload:
                    # the request was admitted while this prefetch was in
                    # flight and its swap-in deferred to us: chain the h2d
                    # hop (or, if the blob vanished under a live request —
                    # which pop paths forbid, but stay safe — fall back to
                    # the reactive two-hop load)
                    if staged or self.host.tier_of(key) == "host":
                        self._h2d.submit(_Transfer(H2D, tr.rid, tr.blk,
                                                   tr.seq, tr.nbytes))
                    else:
                        self._submit_transfer_locked(self._disk, req,
                                                     tr.blk, disk_op="load")
                self._wake.notify_all()
            return
        if tr.disk_op == "spill":
            with self._lock:
                self._spill_inflight.discard(key)
                req = self.reqs.get(tr.rid)
                ok = (req is not None and req.state != DONE
                      and tr.blk not in req.pending_reload
                      and tr.blk not in req.inflight)
                if ok:
                    # under the engine lock: admissions also hold it, so a
                    # swap-in can never claim the block between this check
                    # and the spill (which would push the disk read onto
                    # the h2d lane via read-through). The write itself is
                    # one small block; the wire time was slept off-lock.
                    with Span(self.stats, "disk_io_time",
                              "serve.disk.spill", under_lock=1, **meta):
                        if self._pool is not None:
                            # mark this thread as the kv lease's revocation
                            # drain (assumption A2): the spill may only
                            # release — a charge against any undeclared
                            # lease in here would be a blocking edge the
                            # liveness model never saw, and the pool
                            # rejects it loudly
                            with self._pool.draining(self._kv_lease):
                                spilled = self.host.spill(key)
                        else:
                            spilled = self.host.spill(key)
                    self.stats.disk_spill_bytes += spilled
                    # the host copy moved down a tier: its reservation is
                    # what the arbiter has been waiting for
                    self._release_key_locked(key)
                self._wake.notify_all()
            return
        # load: read-through staging is idempotent, so a racy spill/reload
        # interleaving can only change timing, never bytes
        with Span(self.stats, "disk_io_time", "serve.disk.load", **meta):
            self.host.load(key)
        with self._lock:
            self.stats.disk_load_bytes += tr.nbytes
            req = self.reqs.get(tr.rid)
            if req is not None and tr.blk in req.pending_reload:
                self._h2d.submit(_Transfer(H2D, tr.rid, tr.blk, tr.seq,
                                           tr.nbytes))
            elif req is not None:        # swap-in abandoned mid-flight
                req.inflight.discard(tr.blk)
            self._wake.notify_all()

    def _compact_disk(self) -> None:
        """Rewrite the disk tier's log when dead bytes dominate it, on the
        disk stream and off the engine lock (an engine-owned store never
        compacts inside a drop). Puts, drops and reads go on meanwhile."""
        disk = self.host.disk
        if not disk.compact_inline and disk.compaction_due():
            with Span(self.stats, "disk_compact_time", "serve.disk.compact"):
                disk.compact_if_due()

    # ------------------------------------------------------ event applies
    def _apply_events_locked(self) -> None:
        """Apply the DMA streams' completion events. The device work they
        call for (a resume's ``restore_slot``, a swap-out's ``drop_slot``)
        runs after the bookkeeping, each in its own loop phase; the slots
        involved are distinct, so the order of their cache updates does
        not matter."""
        restores: list[tuple[Request, list[dict]]] = []
        drops: list[int] = []
        with self._loop_span("serve.loop.events", "events_time"):
            for ev in self._events:
                if ev[0] == "reload":
                    _, rid, blk, data = ev
                    req = self.reqs.get(rid)
                    if req is None or req.state != RELOADING:
                        self._staged.discard((rid, blk))
                        continue
                    req.reload_data[blk] = data
                    req.pending_reload.discard(blk)
                    if not req.pending_reload:
                        # one per-leaf scatter for the whole resume, not
                        # one full-cache copy per block
                        restores.append((req, [req.reload_data[b] for b in
                                               sorted(req.reload_data)]))
                        req.reload_data.clear()
                        req.state = RUNNING
                        req.quantum = 0
                elif ev[0] == "swap-done":
                    req = self.reqs.get(ev[1])
                    if req is None or req.state != SWAPPING:
                        continue
                    drops.append(req.slot)
                    self._slots[req.slot] = None
                    req.slot = -1
                    req.state = SWAPPED
                    self._swapped.append(req.rid)
            self._events.clear()
        for req, blocks in restores:
            with self._loop_span("serve.kv.restore_slot", "restore_time",
                                 rid=req.rid, blocks=len(blocks)):
                self.kv.restore_slot(req.slot, blocks)
                self.stats.restores += 1
                for blk in range(len(blocks)):
                    if (req.rid, blk) in self._staged:
                        self._staged.discard((req.rid, blk))
                        self.stats.h2d_staged_blocks += 1
                    else:
                        self.stats.h2d_unstaged_blocks += 1
                # the tail block keeps growing after resume: its host copy
                # is stale from now on and must re-offload (every cold
                # block's copy stays valid — reuse_host_copy). Popped only
                # once restore_slot has read it.
                if req.pos % self.cfg.block_size:
                    tail = req.pos // self.cfg.block_size
                    req.mirrored.discard(tail)
                    self.host.pop_offload((req.rid, tail))
                    self._release_key_locked((req.rid, tail))
                if req.t_swap:
                    now = time.monotonic()
                    self.stats.resumes += 1
                    self.stats.swap_stall_time += now - req.t_swap
                    self.stats.swapped_time += req.t_grant - req.t_swap
                    self.stats.reloading_time += now - req.t_grant
                    req.t_swap = 0.0
        for slot in drops:
            with self._loop_span("serve.kv.drop_slot", "drop_time"):
                self.kv.drop_slot(slot)

    # ----------------------------------------------------- admission path
    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.batch_buckets:
            if n <= b:
                return b
        return self.cfg.batch_buckets[-1]

    def _plan_admissions_locked(self) -> list[tuple[int, int]]:
        """Assign free slots: swapped requests first (schedule their
        reloads), then fresh requests (returned for batched prefill).
        Grows the cache to the next batch bucket when demand requires."""
        want = len(self._swapped) + len(self._queue)
        if want == 0:
            return []
        occupied = sum(r is not None for r in self._slots)
        desired = self._bucket_for(occupied + want)
        if self.kv is None:
            self.kv = PagedKVCache(
                self.model, desired, self.cfg.max_len,
                block_size=self.cfg.block_size, device=self.device)
            self._slots = [None] * desired
        elif desired > self.kv.bucket:
            self.kv.grow(desired)
            self._slots.extend([None] * (desired - len(self._slots)))
        free = [s for s, r in enumerate(self._slots) if r is None]

        # fresh requests admit before swapped resumes: a preemption's whole
        # point is to let waiters in, so the preempted request must not
        # reclaim its slot ahead of them (a production engine would add an
        # aging term here to bound swapped-out residence)
        admits: list[tuple[int, int]] = []
        now = time.monotonic()
        while free and self._queue:
            rid = self._queue.pop(0)
            slot = free.pop(0)
            self._slots[slot] = rid
            self.reqs[rid].slot = slot
            admits.append((slot, rid))
            self.stats.admissions += 1
            self.stats.queue_time += now - self.reqs[rid].t_submit

        # swap-ins: host-resident blocks reload through the h2d stream;
        # disk-resident blocks take the pipelined two-hop chain (disk
        # stream load first, h2d hop chained on its completion). A block
        # whose prefetch is already queued/in service is NOT resubmitted —
        # the prefetch handler chains the h2d hop itself — so the disk
        # stream never sleeps a wire time staging the same blob twice.
        while free and self._swapped:
            rid = self._swapped[0]
            req = self.reqs[rid]
            blocks = range(self.kv.n_token_blocks(req.pos))
            if self._pool is not None:
                # reserve the resume's host-side staging before taking the
                # slot: disk-resident blocks land in host RAM on their way
                # up, and admitting a request whose staging cannot be
                # charged would burst past the arbitrated share. A refusal
                # defers the admission (FIFO preserved: later swapped
                # requests wait too) and the recorded pressure drives the
                # spill stream until the resume fits.
                charged_now = []
                ok = True
                for blk in blocks:
                    key = (rid, blk)
                    if (key in self._charged
                            or key in self._prefetch_inflight
                            or not self._tiered
                            or self.host.tier_of(key) != "disk"):
                        continue
                    if self._charge_key_locked(key, self._kv_lease):
                        charged_now.append(key)
                    else:
                        ok = False
                        break
                if not ok:
                    for key in charged_now:
                        self._release_key_locked(key)
                    break
                for blk in blocks:
                    # staged (or in-flight) prefetches now back a resuming
                    # request: their bytes outrank opportunistic staging,
                    # so the reservation migrates prefetch -> kv
                    self._transfer_key_locked((rid, blk), self._kv_lease)
            self._swapped.pop(0)
            slot = free.pop(0)
            self._slots[slot] = rid
            req.slot = slot
            req.state = RELOADING
            req.t_grant = now
            req.pending_reload = set(blocks)
            for blk in blocks:
                if (rid, blk) in self._prefetch_inflight:
                    req.inflight.add(blk)   # h2d chains on the prefetch
                elif (self._tiered
                        and self.host.tier_of((rid, blk)) == "disk"):
                    self._submit_transfer_locked(self._disk, req, blk,
                                                 disk_op="load")
                else:
                    self._submit_transfer_locked(self._h2d, req, blk)
        return admits

    def _submit_transfer_locked(self, stream: _DmaStream, req: Request,
                                blk: int, *, disk_op: str = "") -> None:
        key = (req.rid, blk)
        if key not in self._block_seq:
            self._block_seq[key] = self._seq_counter
            self._seq_counter += 1
        req.inflight.add(blk)
        stream.submit(_Transfer(stream.kind, req.rid, blk,
                                self._block_seq[key], self.kv.block_nbytes,
                                disk_op=disk_op))

    # ------------------------------------------------------ model programs
    def _run_program(self, fn, *args):
        """Run jitted ``fn`` on ``(self.params, *args)``. The first call at
        a new argument signature compiles explicitly, announced through
        ``on_compile``. The key skips ``params``: they are fixed for the
        engine's life."""
        key = (fn, tuple((a.shape, a.dtype) for a in jax.tree.leaves(args)))
        exe = self._programs.get(key)
        if exe is None:
            if self.on_compile is not None:
                self.on_compile(self)
            exe = self._programs[key] = self._compile(fn, args)
        return exe(self.params, *args)

    def _compile(self, fn, args):
        return fn.lower(self.params, *args).compile()

    # ------------------------------------------------------------ prefill
    def _prefill_admit(self, admits: list[tuple[int, int]]) -> None:
        """One batched forward over the admitted prompts (padded to a
        (bucket, block-aligned-length) static shape), then scatter the K/V
        into the admitted slots and sample each request's first token."""
        cfg = self.cfg
        with self._loop_span("serve.loop.admit", "admit_time"):
            reqs = [self.reqs[rid] for _, rid in admits]
            max_p = max(len(r.prompt) for r in reqs)
            s_pad = min(-(-max_p // cfg.block_size) * cfg.block_size,
                        cfg.max_len)
            b_pad = self._bucket_for(len(reqs))
            toks = np.zeros((b_pad, s_pad), np.int32)
            lengths = np.ones((b_pad,), np.int32)
            for i, r in enumerate(reqs):
                toks[i, :len(r.prompt)] = r.prompt
                lengths[i] = len(r.prompt)
        with self._loop_span("serve.prefill", "prefill_time", rows=len(reqs),
                             bucket=b_pad, padded_len=s_pad):
            logits, kv = self._run_program(self._prefill, self.kv.put(toks),
                                           self.kv.put(lengths))
            logits_np = np.asarray(logits, np.float32)
        with self._loop_lock(), self._loop_span("serve.kv.scatter_prefill",
                                                "scatter_time"):
            rows = jax.tree.map(lambda a: a[:, :len(reqs)], kv)
            self.kv.scatter_prefill([slot for slot, _ in admits], rows)
            for i, (slot, rid) in enumerate(admits):
                req = self.reqs[rid]
                req.pos = len(req.prompt)
                req.state = RUNNING
                req.quantum = 0
                self.stats.prefill_tokens += req.pos
                self.stats.kv_bytes_written += int(
                    req.pos * self.kv.token_nbytes)
                self._emit_locked(req, logits_np[i])

    def _emit_locked(self, req: Request, row_logits: np.ndarray) -> None:
        tok = _sample_token(row_logits, seed=self._seed, rid=req.rid,
                            pos=req.pos, temperature=self.cfg.temperature,
                            vocab_size=self.model.cfg.vocab_size)
        req.out.append(tok)
        req.last = tok
        if self.on_token is not None:
            self.on_token(req, row_logits)
        if req.t_first == 0.0:      # a migrated request keeps its original
            req.t_first = time.monotonic()   # first-token stamp (ticket)
        self.stats.tokens += 1
        if len(req.out) >= req.max_new or req.pos >= self.cfg.max_len:
            self._finish_locked(req)

    def _finish_locked(self, req: Request) -> None:
        req.state = DONE
        self._live.discard(req.rid)
        if req.slot >= 0:
            self._slots[req.slot] = None
            req.slot = -1
        for blk in req.mirrored:
            self.host.pop_offload((req.rid, blk))
            self._release_key_locked((req.rid, blk))
        req.mirrored.clear()
        req.pending_reload.clear()
        for blk in range(self.kv.n_token_blocks(req.pos)):
            self._block_seq.pop((req.rid, blk), None)
            self._staged.discard((req.rid, blk))
        # in-flight d2h mirrors see state == DONE and drop their payload
        # (and release their reservations); in-flight prefetches release
        # theirs on completion when no host bytes landed

    # ------------------------------------------------- offload scheduling
    def _schedule_offload_locked(self) -> None:
        """Mirror cold blocks of running rows to the host store (eager d2h
        that overlaps under decode; makes a later swap-out nearly free)."""
        cfg = self.cfg
        if not cfg.offload or self.kv is None:
            return
        for slot, rid in enumerate(self._slots):
            if rid is None:
                continue
            req = self.reqs[rid]
            if req.state != RUNNING:
                continue
            cold = max(req.pos - cfg.hot_window, 0) // cfg.block_size
            cap = int(cfg.offload_fraction
                      * self.kv.n_token_blocks(req.pos))
            for blk in range(min(cold, cap)):
                if blk not in req.mirrored and blk not in req.inflight:
                    # shared pool: reserve before the bytes move; a refusal
                    # defers this (and every later) mirror until the spill
                    # stream frees share — eager mirroring is optional
                    # work, never worth bursting the budget for
                    if not self._charge_key_locked((rid, blk),
                                                   self._kv_lease):
                        return
                    self._submit_transfer_locked(self._d2h, req, blk)

    def _schedule_spill_locked(self) -> None:
        """Second threshold of the hierarchy: once the host KV mirror
        passes ``host_kv_bytes``, push the least-recently-used mirrored
        blocks down to the disk tier. Runs on the dedicated disk stream
        (never the h2d/d2h DMA lanes); victim choice is LRU because at
        runtime the request future is unknown — the serving counterpart of
        the compiler's Belady-over-the-schedule spills."""
        if not self._tiered or self._disk is None or self.kv is None:
            return
        blk_n = self.kv.block_nbytes
        if self._pool is not None:
            # arbitrated budget: drain (a) bytes held past the current
            # grants — a revocation leaves `overage` and fires the
            # pressure callback — and (b) the recorded deficit of refused
            # reservations, so deferred transfers eventually fit. Spills
            # already in flight count as freed.
            with self._revoke_lock:
                if self._revoked_pending:
                    self.stats.revocations += 1
                    self._revoked_pending = 0
            budget = (self._kv_lease.overage + self._kv_lease.pressure
                      + self._pf_lease.overage + self._pf_lease.pressure
                      - len(self._spill_inflight) * blk_n)
        else:
            cap = self.cfg.host_kv_bytes
            if cap is None:
                return
            budget = (self.host.resident_bytes
                      - len(self._spill_inflight) * blk_n - cap)
        if budget <= 0:
            return
        for key in self.host.lru_keys():
            if budget <= 0:
                break
            if (key not in self._block_seq or key in self._spill_inflight
                    or key in self._prefetch_inflight):
                continue                    # not a serving block / queued
            rid, blk = key
            req = self.reqs.get(rid)
            if (req is None or req.state == RELOADING
                    or blk in req.inflight or blk in req.pending_reload):
                continue
            self._spill_inflight.add(key)
            self._disk.submit(_Transfer(DISK, rid, blk,
                                        self._block_seq[key],
                                        self.kv.block_nbytes,
                                        disk_op="spill"))
            budget -= self.kv.block_nbytes

    def _schedule_prefetch_locked(self) -> None:
        """NEO-style predictive reload: the swapped queue *is* the resume
        schedule, so stage the next-scheduled requests' disk-resident
        blocks back into host RAM while decode runs — by admission time
        only the h2d hop remains. Strictly headroom-bounded: a prefetch
        never pushes occupancy past ``host_kv_bytes`` (it could only thrash
        with the LRU spiller), and prefetch/spill never race on one block
        (each skips keys the other has in flight)."""
        cfg = self.cfg
        cap = cfg.host_kv_bytes
        if (not cfg.prefetch_swapped or not self._tiered
                or self._disk is None or self.kv is None):
            return
        if self._pool is None and cap is None:
            return
        if self._pool is None:
            # reserve headroom for everything already headed host-side:
            # our own in-flight prefetches, resuming requests' pending
            # two-hop reloads (their disk legs stage into the host arena
            # when they land), and in-flight d2h offload mirrors
            # (put_offload on arrival). Conservative for blocks already
            # staged or h2d-only — over-reserving only makes the
            # prefetcher more cautious, never an over-commit
            reserved = len(self._prefetch_inflight) + sum(
                len(self.reqs[r].pending_reload | self.reqs[r].inflight)
                for r in self._live)
            headroom = (cap - self.host.resident_bytes
                        - reserved * self.kv.block_nbytes)
        for rid in self._swapped:
            if self._pool is None and headroom < self.kv.block_nbytes:
                return
            req = self.reqs.get(rid)
            if req is None:
                continue
            for blk in range(self.kv.n_token_blocks(req.pos)):
                if self._pool is None and headroom < self.kv.block_nbytes:
                    return
                key = (rid, blk)
                if (key in self._prefetch_inflight
                        or key in self._spill_inflight
                        or self.host.tier_of(key) != "disk"):
                    continue
                if self._pool is not None:
                    if self._kv_lease.pressure > 0:
                        # mandatory work is waiting on the spill stream:
                        # staging now would hand the spiller fresh LRU
                        # victims and churn the disk stream in a loop
                        # (stage → spill-for-pressure → restage) without
                        # ever helping the blocked resume
                        return
                    # the prefetch lease IS the headroom: an opportunistic
                    # (non-urgent) reservation that never records
                    # pressure — a refusal just means no staging now
                    if not self._charge_key_locked(key, self._pf_lease,
                                                   urgent=False):
                        return
                self._prefetch_inflight.add(key)
                self._disk.submit(_Transfer(
                    DISK, rid, blk, self._block_seq.get(key, 0),
                    self.kv.block_nbytes, disk_op="prefetch"))
                if self._pool is None:
                    headroom -= self.kv.block_nbytes

    def _schedule_preempt_locked(self) -> None:
        """Swap out requests that exhausted their decode quantum while
        others wait — the continuous-batching fairness lever, and the
        source of genuine reload traffic."""
        cfg = self.cfg
        if not cfg.preempt_every or self.kv is None:
            return
        waiting = len(self._queue) + len(self._swapped)
        for slot, rid in enumerate(self._slots):
            if waiting <= 0:
                return
            if rid is None:
                continue
            req = self.reqs[rid]
            if req.state != RUNNING or req.quantum < cfg.preempt_every:
                continue
            if len(req.out) >= req.max_new - 1:     # about to finish anyway
                continue
            pending = [blk for blk in range(self.kv.n_token_blocks(req.pos))
                       if blk not in req.mirrored and blk not in req.inflight]
            if self._pool is not None:
                # a swap-out must mirror *every* unmirrored block — all or
                # nothing. Reserve the full set up front; if the share
                # cannot take it, skip preempting this request this round
                # (the recorded pressure spills other blocks; we retry on
                # the next pass) rather than strand it half-swapped
                charged_now = []
                ok = True
                for blk in pending:
                    key = (rid, blk)
                    if key in self._charged:
                        continue
                    if self._charge_key_locked(key, self._kv_lease):
                        charged_now.append(key)
                    else:
                        ok = False
                        break
                if not ok:
                    for key in charged_now:
                        self._release_key_locked(key)
                    continue
            req.state = SWAPPING
            req.t_swap = time.monotonic()
            self.stats.swaps += 1
            waiting -= 1
            for blk in pending:
                self._submit_transfer_locked(self._d2h, req, blk)
            if not req.inflight:                    # everything was mirrored
                self._events.append(("swap-done", rid))

    # -------------------------------------------------------------- decode
    def _decode_inputs_locked(self, active: list[tuple[int, int]]):
        """The next decode step's cache and host inputs: the feed token,
        cache length and live mask of every slot."""
        self._idle_spins = 0                   # decode is forward progress
        bucket = self.kv.bucket
        toks = np.zeros((bucket, 1), np.int32)
        lens = np.zeros((bucket,), np.int32)
        mask = np.zeros((bucket,), bool)
        for slot, rid in active:
            req = self.reqs[rid]
            toks[slot, 0] = req.last
            lens[slot] = req.pos
            mask[slot] = True
        return self.kv.cache, toks, lens, mask

    def _decode_once(self, active: list[tuple[int, int]], inputs) -> None:
        cache, toks, lens, mask = inputs
        with self._loop_span("serve.decode", "decode_time", rows=len(active),
                             bucket=len(lens)):
            put = self.kv.put
            logits, new_cache = self._run_program(
                self._step, cache, put(toks), put(lens), put(mask))
            logits_np = np.asarray(logits, np.float32)
        with self._loop_lock(), self._loop_span("serve.decode.emit",
                                                "emit_time"):
            self.stats.decode_steps += 1
            self.kv.cache = new_cache
            for slot, rid in active:
                req = self.reqs[rid]
                req.pos += 1
                req.quantum += 1
                self.stats.kv_bytes_written += int(self.kv.token_nbytes)
                self.stats.decode_tokens += 1
                self._emit_locked(req, logits_np[slot])

    def _stall_wait(self) -> None:
        """Nothing resident to decode: wait for a DMA completion event."""
        with self._loop_span("serve.loop.stall", "stall_time"), self._wake:
            busy = (self._events or self._d2h.pending or self._h2d.pending
                    or (self._disk is not None and self._disk.pending)
                    or self._spill_inflight or self._prefetch_inflight
                    or any(self.reqs[r].inflight for r in self._live))
            if not busy and not self._queue and not self._swapped:
                raise RuntimeError(
                    f"serving scheduler wedged on replica {self.name!r} — "
                    f"live waits-for graph: {self._waits_for_locked()}")
            if busy:
                self._idle_spins = 0
            elif self._pool is not None:
                # deferred admissions with nothing in flight: room must
                # come from our own spills or from a co-consumer draining
                # its share. Any movement of pool occupancy or our grant
                # is progress (the other consumer may just be slow — not
                # deadlocked), so the counter resets on it; only a pool
                # that is provably static gets the loud failure.
                state = (self._pool.used_bytes, self._kv_lease.grant)
                if state != self._idle_pool_state:
                    self._idle_pool_state = state
                    self._idle_spins = 0
                self._idle_spins += 1
                if self._idle_spins > 100:
                    waits = self._waits_for_locked()
                    if self._certified_live:
                        # DESIGN.md §14 assumption A4: this configuration
                        # was statically proven stall-free, so reaching
                        # here means the certifier is unsound or a
                        # blocking edge escaped the model — not an
                        # operational deadlock to shrug at
                        raise LivenessModelError(
                            "no-progress detector fired on replica "
                            f"{self.name!r} under a liveness-certified "
                            "pool configuration (statically unreachable): "
                            "the certifier is unsound or the runtime grew "
                            "a blocking edge outside the model — live "
                            f"waits-for graph: {waits}")
                    raise RuntimeError(
                        f"shared-pool deadlock on replica {self.name!r}: "
                        "swapped requests cannot reserve their resume "
                        "staging, no spillable bytes remain, and no other "
                        "consumer is releasing any — live waits-for "
                        f"graph: {waits}")
            self._wake.wait(timeout=0.1)


# --------------------------------------------------------------------------
# the unbatched oracle
# --------------------------------------------------------------------------
def naive_generate(model, params, prompt, *, max_new: int = 32,
                   max_len: int = 512, rid: int = 0, seed: int = 0,
                   temperature: float = 0.0, return_logits: bool = False,
                   force: list[int] | None = None):
    """Reference decode for ONE request, no batching/padding/offload: one
    prefill forward, then single-row decode steps, sampling with the same
    (seed, rid, position) key schedule as the engine. ``Engine.generate``
    must reproduce this for every batching and offload configuration.

    ``return_logits``: return ``(tokens, rows)``, where ``rows[i]`` is the
    float32 logit row (vocab padding cut) token ``i`` was sampled from —
    what a low-precision comparison needs to tell a near-tie from a
    real divergence.

    ``force``: feed these tokens to the next steps in place of the sampled
    ones (teacher forcing). ``tokens[i]`` and ``rows[i]`` then score
    position ``i`` of another decode's output given its own prefix, so
    that decode is checked at every position, not only up to its first
    departure."""
    prompt = [int(t) for t in prompt]
    p_len = len(prompt)
    vocab = model.cfg.vocab_size
    # jit wrappers cached on the model: jax.jit keys its trace cache on
    # wrapper identity, so a fresh wrapper per oracle call would recompile
    # decode_step for every request of every test
    fns = getattr(model, "_serve_oracle_fns", None)
    if fns is None:
        fns = (jax.jit(model.prefill), jax.jit(model.decode_step))
        model._serve_oracle_fns = fns
    prefill, step = fns
    logits, kv = prefill(params, jnp.asarray([prompt], jnp.int32),
                         jnp.asarray([p_len], jnp.int32))
    cache = model.init_cache(1, max_len)
    cache = {k: cache[k].at[:, :, :p_len].set(kv[k].astype(cache[k].dtype))
             for k in cache}
    out: list[int] = []
    rows: list[np.ndarray] = []
    pos = p_len
    row = np.asarray(logits[0], np.float32)
    while True:
        tok = _sample_token(row, seed=seed, rid=rid, pos=pos,
                            temperature=temperature, vocab_size=vocab)
        out.append(tok)
        if return_logits:
            rows.append(row[:vocab])
        if len(out) >= max_new or pos >= max_len:
            return (out, rows) if return_logits else out
        if force is not None and len(out) <= len(force):
            tok = int(force[len(out) - 1])
        logits, cache = step(params, cache,
                             jnp.asarray([[tok]], jnp.int32),
                             jnp.asarray([pos], jnp.int32))
        row = np.asarray(logits[0], np.float32)
        pos += 1
