"""Fleet-scale serving: a router over N engine replicas (DESIGN.md §16).

The :class:`Router` owns a shared admission queue in front of
``topology.n_replicas`` independent :class:`~repro.serve.Engine` replicas,
each with its own host/disk tier population (and, when
``topology.host_bytes_per_replica`` is set, its own arbitrated
:class:`~repro.core.pool.HostPool`). Three mechanisms make it a fleet and
not just N engines:

* **Placement** — every admission picks a replica through a pluggable
  policy (:data:`PLACEMENT_POLICY_NAMES`) reading the live
  :meth:`Engine.load` signals. Request ids are allocated *globally* by the
  router and pinned with ``submit(rid=)``: the sampling key schedule folds
  only ``(seed, rid, position)``, so a request's tokens are identical
  wherever it lands — placement, like dispatch order inside one replica,
  changes timing and never bytes (the TURNIP property, lifted one level).

* **Migration** — swapped requests move between replicas as
  :class:`~repro.serve.MigrationTicket` payloads serialized through
  :func:`encode_ticket` / :func:`decode_ticket` — the same framed-record
  format as the disk tier's ``spill.log`` (magic + length header per
  payload), shipped over a dedicated inter-replica transfer stream
  (:class:`_NicStream`) whose wire time is priced with the same constants
  as the simulator's sixth channel (``HardwareModel.nic_bw``), so
  :func:`~repro.core.simulate.migration_crossover` predicts when shipping
  KV beats re-prefilling it. Import is **all-or-nothing**
  (:meth:`Engine.import_migration`): a refused ticket leaves no byte,
  charge, or record on the destination and falls back to cold re-prefill
  of ``prompt + out`` — token-exact either way.

* **Drain** — each replica's run loop beats a
  :class:`~repro.ft.supervisor.Heartbeat` (and announces each model
  compile as a bounded grace, :data:`COMPILE_GRACE_S`, since a compile
  stops the loop for seconds); a replica that crashes
  (:class:`~repro.serve.ReplicaKilled`) or goes silent (missed heartbeats
  — the pause/wedge failure mode) is drained: taken out of placement,
  hard-killed, its worker joined (so its DMA streams are joined and no
  thread leaks), every in-flight request checkpointed at its last emitted
  token (:meth:`Engine.drain_tickets` — host/disk tiers are owned by the
  host process and survive the dead worker, so SWAPPED requests ship
  *warm*), shipped to survivors, and resumed token-exact.

Lock order (audited by the suite-wide sanitizer): Router → ServeEngine;
Heartbeat and NicStream are leaves; no path ever holds two ServeEngine
locks at once.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import logging
import random
import threading
import time

import jax
import numpy as np

from ..core import lockcheck
from ..core.pool import HostPool
from ..core.stores import DiskStore
from ..ft.supervisor import Heartbeat
from ..launch.mesh import FleetTopology
from .engine import (DONE, Engine, MigrationRefused, MigrationTicket,
                     ReplicaKilled, ServeConfig)
from .spans import Span

__all__ = ["Router", "RouterStats", "PLACEMENT_POLICY_NAMES",
           "PlacementPolicy", "get_placement",
           "encode_ticket", "decode_ticket"]

log = logging.getLogger(__name__)

# a replica compiling a model program for a new shape does not beat until
# the compile ends (seconds at full size): it is declared dead only after
# this much silence on top of the heartbeat timeout
COMPILE_GRACE_S = 300.0


# --------------------------------------------------------------------------
# wire codec — spill.log's framed-record format, reused verbatim
# --------------------------------------------------------------------------
# One ticket on the wire is a sequence of records, each framed exactly like
# a DiskStore spill.log record (magic + payload length, then raw bytes):
# first a JSON header (identity, progress, per-block leaf specs), then — for
# a warm ticket — one record per (block, leaf) payload in sorted leaf order.
# Reusing the frame means the same torn-record/bad-magic checks guard both
# the disk tier and the inter-replica link, and a migration blob is exactly
# what the disk tier would have logged for the same blocks.
_MAGIC = DiskStore._MAGIC
_HDR = DiskStore._HDR


def _frame(payload: bytes) -> bytes:
    return _HDR.pack(_MAGIC, len(payload)) + payload


def _unframe(data: bytes, off: int) -> tuple[bytes, int]:
    hdr = data[off:off + _HDR.size]
    if len(hdr) != _HDR.size:
        raise ValueError("torn migration record header")
    magic, n = _HDR.unpack(hdr)
    if magic != _MAGIC:
        raise ValueError(f"bad migration record magic {magic!r}")
    off += _HDR.size
    payload = data[off:off + n]
    if len(payload) != n:
        raise ValueError(f"torn migration record payload: "
                         f"{len(payload)}/{n} bytes")
    return payload, off + n


def encode_ticket(t: MigrationTicket) -> bytes:
    """Serialize a ticket to one self-describing blob, bit-exact."""
    blocks = t.blocks if t.blocks is not None else []
    arrs = [[(k, np.ascontiguousarray(np.asarray(b[k]))) for k in sorted(b)]
            for b in blocks]
    head = {
        "rid": t.rid, "prompt": list(map(int, t.prompt)),
        "out": list(map(int, t.out)), "max_new": t.max_new,
        "pos": t.pos, "last": t.last, "block_size": t.block_size,
        "t_submit": t.t_submit, "t_first": t.t_first,
        "warm": t.blocks is not None,
        "blocks": [[[k, list(a.shape), str(a.dtype)] for k, a in blk]
                   for blk in arrs],
    }
    parts = [_frame(json.dumps(head).encode())]
    for blk in arrs:
        for _, a in blk:
            parts.append(_frame(a.tobytes()))
    return b"".join(parts)


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, including the ml_dtypes families (bfloat16,
    float8_*) jax caches use but plain numpy cannot look up by name."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def decode_ticket(data: bytes) -> MigrationTicket:
    """Inverse of :func:`encode_ticket`; validates every frame and refuses
    trailing bytes, so a truncated or corrupted ship fails loudly instead
    of landing garbage KV."""
    head_b, off = _unframe(data, 0)
    head = json.loads(head_b.decode())
    blocks = None
    if head["warm"]:
        blocks = []
        for specs in head["blocks"]:
            blk = {}
            for name, shape, dtype in specs:
                payload, off = _unframe(data, off)
                arr = np.frombuffer(payload, dtype=_np_dtype(dtype))
                blk[name] = arr.reshape(tuple(shape))
            blocks.append(blk)
    if off != len(data):
        raise ValueError(f"{len(data) - off} trailing bytes after ticket")
    return MigrationTicket(
        rid=head["rid"], prompt=list(head["prompt"]), out=list(head["out"]),
        max_new=head["max_new"], pos=head["pos"], last=head["last"],
        block_size=head["block_size"], t_submit=head["t_submit"],
        t_first=head["t_first"], blocks=blocks)


# --------------------------------------------------------------------------
# placement policies
# --------------------------------------------------------------------------
PLACEMENT_POLICY_NAMES = ("least-loaded", "join-shortest-kv", "random")


class PlacementPolicy:
    """Pick a replica for an admission (or a migration target) from the
    alive set. Policies read :meth:`Engine.load` — they change *where* a
    request runs, never *what* it emits (the rid rides with it)."""

    name = "base"

    def pick(self, replicas: "list[_Replica]") -> "_Replica":
        raise NotImplementedError


class LeastLoadedPlacement(PlacementPolicy):
    """Fewest live requests wins; ties break on replica index."""

    name = "least-loaded"

    def pick(self, replicas):
        return min(replicas, key=lambda r: (r.engine.load()[0], r.index))


class JoinShortestKVPlacement(PlacementPolicy):
    """Fewest resident+committed KV tokens wins — the memory-pressure
    analogue of join-shortest-queue; ties break on replica index."""

    name = "join-shortest-kv"

    def pick(self, replicas):
        return min(replicas, key=lambda r: (r.engine.load()[1], r.index))


class RandomPlacement(PlacementPolicy):
    """Seeded uniform choice — the chaos harness's adversarial baseline."""

    name = "random"

    def __init__(self, seed: int = 0) -> None:
        self._rng = random.Random(seed)

    def pick(self, replicas):
        return self._rng.choice(replicas)


def get_placement(policy: str | PlacementPolicy | None, *,
                  seed: int = 0) -> PlacementPolicy:
    if isinstance(policy, PlacementPolicy):
        return policy
    if policy is None or policy == "least-loaded":
        return LeastLoadedPlacement()
    if policy == "join-shortest-kv":
        return JoinShortestKVPlacement()
    if policy == "random":
        return RandomPlacement(seed)
    raise ValueError(f"unknown placement policy {policy!r} "
                     f"(have {PLACEMENT_POLICY_NAMES})")


# --------------------------------------------------------------------------
# the inter-replica transfer stream
# --------------------------------------------------------------------------
class _NicStream(threading.Thread):
    """The fleet's sixth engine class at runtime: one dedicated thread
    serving framed ticket blobs FIFO, sleeping the simulated wire time
    (``latency + nbytes / bw`` — the same cost model as the simulator's
    NIC channel) before invoking the delivery callback. Deliveries run on
    this thread with no router lock held, so an import that takes the
    destination's engine lock can never deadlock against the router."""

    def __init__(self, bw: float, latency: float) -> None:
        super().__init__(name="nic", daemon=True)
        self.bw = bw
        self.latency = latency
        self._cond = threading.Condition(lockcheck.make_lock("NicStream"))
        self._queue: collections.deque = collections.deque()
        self._shutdown = False
        self.shipped_bytes = 0
        self.transfers = 0

    def send(self, data: bytes, deliver) -> tuple[threading.Event, dict]:
        """Enqueue one blob; returns ``(done, box)`` — ``done`` is set
        after delivery, ``box['error']`` carries a delivery exception."""
        done = threading.Event()
        box: dict = {}
        with self._cond:
            if self._shutdown:
                raise RuntimeError("nic stream is shut down")
            self._queue.append((data, deliver, done, box))
            self._cond.notify_all()
        return done, box

    def shutdown(self) -> None:
        with self._cond:
            self._shutdown = True
            self._cond.notify_all()

    def run(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._shutdown:
                    self._cond.wait()
                if not self._queue:
                    return
                data, deliver, done, box = self._queue.popleft()
            time.sleep(self.latency + len(data) / self.bw)
            try:
                deliver(data)
            except BaseException as e:   # noqa: BLE001 — surfaced via box
                box["error"] = e
            finally:
                with self._cond:
                    self.shipped_bytes += len(data)
                    self.transfers += 1
                done.set()


# --------------------------------------------------------------------------
# the router
# --------------------------------------------------------------------------
@dataclasses.dataclass
class _Replica:
    index: int
    name: str
    engine: Engine
    pool: HostPool | None
    thread: threading.Thread | None = None
    alive: bool = True
    fault: BaseException | None = None
    closed: bool = False


@dataclasses.dataclass
class RouterStats:
    submitted: int = 0
    completed: int = 0
    migrations: int = 0          # warm tickets delivered (drain + rebalance)
    migrated_bytes: int = 0      # wire bytes of delivered warm tickets
    reprefills: int = 0          # cold fallbacks (device state lost)
    replicas_killed: int = 0
    drain_time: float = 0.0      # wall seconds spent draining dead replicas
    #                              (the serve.router.drain span)
    ttft_p99: dict[str, float] = dataclasses.field(default_factory=dict)
    # one record per drained replica: its name, the cause ("heartbeat" or
    # the fault's repr), and for a heartbeat drain the engine loop phase
    # that was open and for how many seconds it had been open
    drains: list[dict] = dataclasses.field(default_factory=list)


class Router:
    """N serving replicas behind one admission queue. See module docstring
    for the design; the operational surface is::

        with Router(model, params, cfg, topology=topo) as router:
            rids = [router.submit(p, max_new=32) for p in prompts]
            router.wait(rids)
            outs = [router.result(r) for r in rids]

    Replica worker threads start at construction and idle cheaply between
    bursts; :meth:`close` (or the context exit) joins every thread the
    router ever started."""

    def __init__(self, model, params, cfg: ServeConfig = ServeConfig(), *,
                 topology: FleetTopology | None = None,
                 placement: str | PlacementPolicy = "least-loaded",
                 seed: int | None = None) -> None:
        self.topology = topology if topology is not None else FleetTopology()
        self.cfg = cfg
        if seed is None:
            seed = cfg.seed
        self.placement = get_placement(placement, seed=seed)
        self._lock = lockcheck.make_lock("Router")
        self._cond = threading.Condition(self._lock)
        self.heartbeat = Heartbeat(
            timeout_s=self.topology.heartbeat_timeout_s)
        self.nic = _NicStream(self.topology.nic_bw, self.topology.nic_latency)
        self.stats = RouterStats()
        self._records: dict[int, dict] = {}
        self._admit: collections.deque = collections.deque()
        self._next_rid = 0
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self.replicas: list[_Replica] = []
        # one accelerator per replica, round-robin over what this process
        # sees: four replicas on a four-chip host each own a chip
        devices = jax.devices()
        for i, name in enumerate(self.topology.replica_names):
            pool = (HostPool(self.topology.host_bytes_per_replica)
                    if self.topology.host_bytes_per_replica else None)
            eng = Engine(model, params, cfg, pool=pool, name=name,
                         device=devices[i % len(devices)])
            # each run-loop iteration beats the replica's heartbeat OFF the
            # engine lock; a wedged/paused loop stops beating and the
            # monitor drains it
            eng.on_step = (lambda _eng, _name=name:
                           self.heartbeat.beat(_name))
            # a compile silences the loop for a bounded time: announce it
            eng.on_compile = (lambda _eng, _name=name: self.heartbeat.beat(
                _name, grace_s=COMPILE_GRACE_S))
            self.replicas.append(_Replica(i, name, eng, pool))
        self.nic.start()
        for rep in self.replicas:
            rep.thread = threading.Thread(
                target=self._worker, args=(rep,),
                name=f"router-{rep.name}", daemon=True)
            rep.thread.start()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="router-monitor", daemon=True)
        self._monitor.start()

    # --------------------------------------------------------- admission
    def submit(self, prompt, max_new: int = 32) -> int:
        """Enqueue a request on the shared admission queue; returns its
        globally unique rid (pinned on whichever replica serves it)."""
        prompt = [int(t) for t in prompt]
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self._records[rid] = {
                "prompt": prompt, "max_new": max_new, "prefix": [],
                "replica": None, "done": False,
                "t_submit": time.monotonic(), "t_first": 0.0}
            self._admit.append(rid)
            self.stats.submitted += 1
            self._dispatch_locked()
        return rid

    def _dispatch_locked(self) -> None:
        """Drain the admission queue onto alive replicas (placement-picked).
        With every replica down the queue holds until the monitor notices a
        recovery — requests are never dropped on the floor."""
        while self._admit:
            alive = [r for r in self.replicas if r.alive]
            if not alive:
                return
            rid = self._admit.popleft()
            rec = self._records[rid]
            rep = self.placement.pick(alive)
            rep.engine.submit(rec["prompt"], rec["max_new"], rid=rid)
            rec["replica"] = rep

    # --------------------------------------------------------- results
    def result(self, rid: int) -> list[int]:
        """Tokens emitted so far: the router-held prefix (tokens emitted
        before a cold migration) plus the hosting replica's live tail.
        Complete once :meth:`done` reports True."""
        with self._lock:
            rec = self._records[rid]
            prefix = list(rec["prefix"])
            rep = rec["replica"]
        if rep is None:
            return prefix
        with rep.engine._lock:
            req = rep.engine.reqs.get(rid)
            tail = list(req.out) if req is not None else []
        return prefix + tail

    def done(self, rid: int) -> bool:
        with self._lock:
            rec = self._records[rid]
            if rec["done"]:
                return True
            rep = rec["replica"]
        if rep is None:
            return False
        with rep.engine._lock:
            req = rep.engine.reqs.get(rid)
            finished = req is not None and req.state == DONE
        if finished:
            with self._lock:
                if not rec["done"]:
                    rec["done"] = True
                    self.stats.completed += 1
        return finished

    def wait(self, rids: "list[int] | None" = None,
             timeout: float | None = None) -> None:
        """Block until every request in ``rids`` (default: all submitted)
        completes. Re-raises any router-level fault (a non-kill replica
        crash, a failed drain) rather than hanging on it."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                if self._error is not None:
                    raise self._error
                pending = list(self._records if rids is None else rids)
            if all(self.done(r) for r in pending):
                return
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(
                    f"requests still pending after {timeout}s: "
                    f"{[r for r in pending if not self.done(r)]}")
            time.sleep(0.005)

    # --------------------------------------------------------- fleet loops
    def _worker(self, rep: _Replica) -> None:
        """One replica's driver: run the engine whenever it has live work,
        beat the heartbeat while idle. Exits on router stop or replica
        death; a :class:`ReplicaKilled` raised by the engine marks the
        replica faulted for the monitor to drain."""
        eng = rep.engine
        try:
            while not self._stop.is_set():
                with eng._lock:
                    busy = bool(eng._live)
                    killed = eng._killed
                if killed:
                    # a kill can land while the replica is idle (between
                    # requests); run() would never observe it, so exit
                    # here or the drain's join blocks until router close
                    return
                if not busy:
                    self.heartbeat.beat(rep.name)
                    time.sleep(0.005)
                    continue
                eng.run()
        except ReplicaKilled as e:
            if not self._stop.is_set():
                with self._cond:
                    rep.fault = e
                    self._cond.notify_all()
        except BaseException as e:   # noqa: BLE001 — surfaced via wait()
            with self._cond:
                rep.fault = e
                if not isinstance(e, ReplicaKilled):
                    self._error = e
                self._cond.notify_all()

    def _monitor_loop(self) -> None:
        """Supervision: drain replicas that crashed (worker fault) or went
        silent (missed heartbeats), and keep the admission queue moving."""
        while not self._stop.is_set():
            with self._cond:
                self._cond.wait(timeout=0.02)
                if self._stop.is_set():
                    return
                dead = set(self.heartbeat.dead_workers())
                faulted = [r for r in self.replicas if r.alive
                           and (r.fault is not None or r.name in dead)]
            for rep in faulted:
                try:
                    self._drain_replica(rep, silent=rep.fault is None)
                except BaseException as e:   # noqa: BLE001
                    with self._lock:
                        # a crashed worker's own error names the cause;
                        # a failed drain after it must not hide it
                        if self._error is None:
                            self._error = e
                    return
            with self._lock:
                self._dispatch_locked()

    def _drain_replica(self, rep: _Replica, *, silent: bool = False) -> None:
        """The fault-tolerance path, in the one order that guarantees no
        double execution and no leaked threads: remove from placement →
        hard-kill (idempotent for an already-crashed loop) → resume (a
        paused loop must wake to observe the kill) → join the worker (its
        ``run()`` finally joins every DMA stream) → forget the heartbeat →
        checkpoint every live request → ship each over the NIC (warm
        import, cold re-prefill fallback) → retire the replica's store.

        ``silent``: drained for missed heartbeats. The engine loop phase
        open at that moment, and for how long, goes into
        ``RouterStats.drains``, the log and the span's metadata."""
        phase = rep.engine.phase if silent else None
        record = {"replica": rep.name,
                  "cause": "heartbeat" if silent else repr(rep.fault),
                  "phase": phase[0] if phase else None,
                  "phase_s": time.perf_counter() - phase[1] if phase else 0.0}
        with self._lock:
            if not rep.alive:
                return
            rep.alive = False
            self.stats.drains.append(record)
        log.warning("replica %r drained (%s): engine loop phase %s open "
                    "for %.3f s", rep.name, record["cause"], record["phase"],
                    record["phase_s"])
        with Span(self.stats, "drain_time", "serve.router.drain",
                  replica=rep.name, phase=record["phase"] or "none"):
            rep.engine.hard_kill()
            rep.engine.resume()
            if rep.thread is not None:
                rep.thread.join()
            self.heartbeat.forget(rep.name)
            tickets = rep.engine.drain_tickets()
            for ticket in tickets:
                self._ship(ticket)
            rep.engine.close()
            rep.closed = True
            with self._lock:
                self.stats.replicas_killed += 1

    def _ship(self, ticket: MigrationTicket) -> None:
        """Serialize one ticket, pick a surviving target, push it through
        the transfer stream, and wait for delivery."""
        data = encode_ticket(ticket)
        with self._lock:
            alive = [r for r in self.replicas if r.alive]
        if not alive:
            raise RuntimeError(
                f"request {ticket.rid}: no surviving replica to drain to")
        target = self.placement.pick(alive)
        done, box = self.nic.send(
            data, lambda blob, _t=target: self._deliver(blob, _t))
        done.wait()
        if "error" in box:
            raise box["error"]

    def _deliver(self, data: bytes, target: _Replica) -> None:
        """NIC-thread delivery: decode, try the warm all-or-nothing import,
        fall back to cold re-prefill. Router state is updated *after* the
        engine call, never while holding both locks."""
        ticket = decode_ticket(data)
        if ticket.warm:
            try:
                target.engine.import_migration(ticket)
                with self._lock:
                    rec = self._records.get(ticket.rid)
                    if rec is not None:
                        rec["replica"] = target
                        if ticket.t_first and not rec["t_first"]:
                            rec["t_first"] = ticket.t_first
                    self.stats.migrations += 1
                    self.stats.migrated_bytes += len(data)
                return
            except MigrationRefused:
                pass   # destination kept its invariants; go cold
        self._cold_resume(ticket, target)

    def _cold_resume(self, ticket: MigrationTicket,
                     target: _Replica) -> None:
        """Re-prefill ``prompt + out`` on the target. Token-exact: the next
        sample folds (seed, rid, len(prompt + out)) — exactly the key the
        original continuation would have used — and the emitted tokens so
        far move into the router-held prefix so ``result()`` never loses or
        double-counts them."""
        remaining = ticket.max_new - len(ticket.out)
        with self._lock:
            rec = self._records.get(ticket.rid)
            if rec is not None:
                rec["prefix"].extend(ticket.out)
                rec["replica"] = target
                if ticket.t_first and not rec["t_first"]:
                    rec["t_first"] = ticket.t_first
                if remaining < 1:
                    rec["done"] = True
                    self.stats.completed += 1
                    return
            elif remaining < 1:
                return
            self.stats.reprefills += 1
        target.engine.submit(ticket.prompt + ticket.out, remaining,
                             rid=ticket.rid)

    # --------------------------------------------------------- rebalance
    def rebalance_once(self) -> bool:
        """Live migration (no fault): detach the most-loaded alive
        replica's longest-waiting swapped request and ship it to a
        placement-picked peer. Returns True if a ticket moved."""
        with self._lock:
            alive = [r for r in self.replicas if r.alive]
        if len(alive) < 2:
            return False
        src = max(alive, key=lambda r: (r.engine.load()[0], -r.index))
        ticket = src.engine.export_one_swapped()
        if ticket is None:
            return False
        data = encode_ticket(ticket)
        peers = [r for r in alive if r is not src]
        target = self.placement.pick(peers)
        done, box = self.nic.send(
            data, lambda blob, _t=target: self._deliver(blob, _t))
        done.wait()
        if "error" in box:
            raise box["error"]
        return True

    # --------------------------------------------------------- kill seams
    def kill_replica(self, name: str) -> None:
        """Chaos seam: hard-kill one replica by name (the monitor drains
        it). No-op if it is already dead."""
        for rep in self.replicas:
            if rep.name == name:
                rep.engine.hard_kill()
                return
        raise KeyError(f"no replica named {name!r}")

    # --------------------------------------------------------- accounting
    def ttft_samples(self) -> dict[str, list[float]]:
        """Per-replica time-to-first-token samples (seconds), attributed to
        the replica that finally hosts each request."""
        out: dict[str, list[float]] = {}
        with self._lock:
            recs = [(rid, dict(rec)) for rid, rec in self._records.items()]
        for rid, rec in recs:
            rep = rec["replica"]
            if rep is None:
                continue
            t_first = rec["t_first"]
            if not t_first:
                with rep.engine._lock:
                    req = rep.engine.reqs.get(rid)
                    t_first = req.t_first if req is not None else 0.0
            if t_first:
                out.setdefault(rep.name, []).append(
                    t_first - rec["t_submit"])
        return out

    def summary(self) -> dict:
        """Router-level counters + per-replica p99 TTFT + NIC totals —
        the shape BENCH_9 records."""
        for rid in list(self._records):
            self.done(rid)           # fold any just-finished completions in
        p99 = {name: float(np.percentile(v, 99))
               for name, v in self.ttft_samples().items()}
        with self._lock:
            self.stats.ttft_p99 = p99
            d = dataclasses.asdict(self.stats)
        with self.nic._cond:
            d["nic"] = {"transfers": self.nic.transfers,
                        "shipped_bytes": self.nic.shipped_bytes}
        d["replicas"] = {rep.name: {"alive": rep.alive,
                                    "stats": dataclasses.asdict(
                                        rep.engine.stats)}
                         for rep in self.replicas}
        return d

    # --------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Tear the fleet down: stop the monitor, kill and join every
        worker (a killed run loop joins its DMA streams on the way out),
        drain the NIC, retire every engine store. Idempotent."""
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._monitor.is_alive():
            self._monitor.join()
        for rep in self.replicas:
            rep.engine.hard_kill()
            rep.engine.resume()
            if rep.thread is not None and rep.thread.is_alive():
                rep.thread.join()
        self.nic.shutdown()
        if self.nic.is_alive():
            self.nic.join()
        for rep in self.replicas:
            if not rep.closed:
                rep.engine.close()
                rep.closed = True

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
