"""Fleet chaos harness (DESIGN.md §16): kill a replica mid-decode and
prove nothing changed but the timing.

The claim under test is the TURNIP property lifted to the fleet: placement,
migration, and replica death change *where* and *when* a request's tokens
are produced, never *what* they are. Every chaos run asserts, against the
single-model unbatched oracle (``naive_generate`` with the same
``(seed, rid, position)`` schedule):

* every affected request resumes on a survivor **token-exact** — warm
  (KV shipped over the NIC, bit-exact restore) and cold (re-prefill of
  ``prompt + out``) alike;
* zero leaked threads — the killed replica's run loop joins its DMA
  streams on the way out, the router joins its worker;
* every surviving replica's arbitrated :class:`~repro.core.pool.HostPool`
  stays within capacity at peak and drains to zero after the burst.

Swept over all placement policies × seeded kill instants
(``fault_after_steps`` — deterministic: the replica dies exactly when its
decode-step counter crosses the seed). The slow hypothesis lane widens the
sweep, scaled by ``FUZZ_EXAMPLES``.
"""
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.launch.mesh import FleetTopology, make_fleet_topology
from repro.models import build_model
from repro.serve import (MigrationRefused, MigrationTicket,
                         PLACEMENT_POLICY_NAMES, Engine, ReplicaKilled,
                         Router, ServeConfig, decode_ticket, encode_ticket,
                         get_placement, naive_generate)

KEY = jax.random.PRNGKey(0)
MAX_LEN = 128
SEED = 7
# thread-name prefixes the fleet owns: anything with one of these alive
# after close() is a leak (jax's own pool threads are long-lived and ours
# must not hide among them)
FLEET_THREADS = ("router-", "nic", "serve-dma-")


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(get_arch("olmo-1b"))
    model = build_model(cfg)
    return model, model.init(KEY)


def fleet_cfg(**kw):
    base = dict(max_len=MAX_LEN, batch_buckets=(1, 2), block_size=16,
                offload=True, hot_window=16, preempt_every=2,
                h2d_bw=4e9, d2h_bw=4e9, seed=SEED)
    base.update(kw)
    return ServeConfig(**base)


def make_prompts(model, n, seed=1, lo=17, hi=40):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, model.cfg.vocab_size,
                                       size=int(k))))
            for k in rng.integers(lo, hi, size=n)]


def oracle(lm, prompts, rids, *, max_new):
    model, params = lm
    return [naive_generate(model, params, p, max_new=max_new,
                           max_len=MAX_LEN, rid=r, seed=SEED)
            for p, r in zip(prompts, rids)]


def assert_no_fleet_threads():
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        leaked = [t.name for t in threading.enumerate() if t.is_alive()
                  and any(t.name.startswith(p) for p in FLEET_THREADS)]
        if not leaked:
            return
        time.sleep(0.05)
    assert not leaked, f"fleet threads leaked past close(): {leaked}"


def run_chaos(lm, *, placement, kill_step, prompt_seed=1, n_replicas=3,
              n_prompts=9, max_new=12, kill_index=0):
    """One chaos case: N replicas, one hard-killed once its decode-step
    counter crosses ``kill_step``. Returns the router summary."""
    model, params = lm
    topo = FleetTopology(n_replicas=n_replicas, heartbeat_timeout_s=60.0,
                         host_bytes_per_replica=64 << 20)
    prompts = make_prompts(model, n_prompts, seed=prompt_seed)
    with Router(model, params, fleet_cfg(), topology=topo,
                placement=placement) as router:
        # arm the fault BEFORE any submit: the victim cannot execute a
        # decode step first, so the kill fires at exactly ``kill_step``
        # on every schedule (armed after, a loaded machine can let the
        # victim finish — or even drain — before the counter is live)
        router.replicas[kill_index].engine.fault_after_steps = kill_step
        rids = [router.submit(p, max_new=max_new) for p in prompts]
        router.wait(rids, timeout=300)
        outs = [router.result(r) for r in rids]
        summ = router.summary()
        assert summ["replicas_killed"] == 1
        assert not router.replicas[kill_index].alive
        assert summ["drain_time"] > 0
        for rep in router.replicas:
            if rep.pool is not None and not rep.closed:
                assert rep.pool.peak_bytes <= rep.pool.capacity
                # drain is eventual, not instant: wait() returns at the
                # last DONE, while an in-flight mirror for a finished
                # request releases its charge when its event lands
                deadline = time.monotonic() + 30
                while (not rep.pool.drained
                       and time.monotonic() < deadline):
                    time.sleep(0.005)
                assert rep.pool.drained, rep.pool.snapshot()
    assert outs == oracle(lm, prompts, rids, max_new=max_new)
    assert summ["completed"] == n_prompts
    assert_no_fleet_threads()
    return summ


# ------------------------------------------------------------ chaos sweep
@pytest.mark.parametrize("placement,kill_step",
                         [("least-loaded", 3),
                          ("join-shortest-kv", 6),
                          ("random", 9)])
def test_replica_kill_mid_decode_token_exact(lm, placement, kill_step):
    """The headline chaos case: 1 of 3 replicas hard-killed mid-decode
    (seeded kill instant), swept over every placement policy. All requests
    complete token-exact vs the oracle, no leaked threads, surviving pools
    bounded and drained."""
    summ = run_chaos(lm, placement=placement, kill_step=kill_step)
    # the kill really interrupted in-flight work: the drain shipped
    # something (warm migrations and/or cold re-prefills)
    assert summ["migrations"] + summ["reprefills"] > 0


def test_no_fault_fleet_matches_oracle(lm):
    """Control: the same burst with no kill — pure placement + batching
    across 3 replicas, still token-exact; nothing drained, nothing
    migrated."""
    model, params = lm
    topo = make_fleet_topology(3, heartbeat_timeout_s=60.0)
    prompts = make_prompts(model, 7, seed=2)
    with Router(model, params, fleet_cfg(), topology=topo,
                placement="least-loaded") as router:
        rids = [router.submit(p, max_new=10) for p in prompts]
        router.wait(rids, timeout=300)
        outs = [router.result(r) for r in rids]
        summ = router.summary()
    assert outs == oracle(lm, prompts, rids, max_new=10)
    assert summ["replicas_killed"] == 0
    assert summ["migrations"] == 0 and summ["reprefills"] == 0
    # per-replica TTFT accounting covered every replica that hosted work
    assert summ["ttft_p99"] and all(v > 0 for v in summ["ttft_p99"].values())
    assert_no_fleet_threads()


def test_replicas_take_devices_round_robin(lm):
    """Replica i owns ``jax.devices()[i % n]``: its params and its KV cache
    live there (on a four-chip host, one chip per replica)."""
    model, params = lm
    devices = jax.devices()
    topo = make_fleet_topology(3, heartbeat_timeout_s=60.0)
    with Router(model, params, fleet_cfg(), topology=topo,
                placement="least-loaded") as router:
        rids = [router.submit(p, max_new=2)
                for p in make_prompts(model, 3, seed=3)]
        router.wait(rids, timeout=300)
        for i, rep in enumerate(router.replicas):
            want = {devices[i % len(devices)]}
            assert {rep.engine.device} == want
            assert rep.engine.kv is not None        # one request each
            for tree in (rep.engine.params, rep.engine.kv.cache):
                assert all(a.devices() == want for a in jax.tree.leaves(tree))
    assert_no_fleet_threads()


def test_paused_replica_detected_and_drained(lm):
    """The silent-wedge failure mode: a replica that stops beating without
    crashing (``pause()``) must be drained exactly like a crash — detected
    via missed heartbeats, hard-killed, its requests resumed token-exact
    elsewhere. The beat is backdated to make detection deterministic
    instead of sleeping out a real timeout."""
    model, params = lm
    topo = FleetTopology(n_replicas=2, heartbeat_timeout_s=60.0)
    prompts = make_prompts(model, 6, seed=3)
    with Router(model, params, fleet_cfg(), topology=topo,
                placement="least-loaded") as router:
        rids = [router.submit(p, max_new=10) for p in prompts]
        victim = router.replicas[0]
        # freeze the victim while it provably holds live work: pause()
        # wedges run() at its next iteration, so work observed live under
        # a paused loop can never complete (checking busy before pausing
        # would race the last decode step finishing in the gap)
        deadline = time.monotonic() + 120
        busy = False
        while not busy and time.monotonic() < deadline:
            victim.engine.pause()
            with victim.engine._lock:
                busy = bool(victim.engine._live)
            if not busy:
                victim.engine.resume()
                time.sleep(0.005)
        assert busy, "victim never picked up work"
        router.heartbeat.beat(victim.name,
                              now=time.monotonic() - 2 * 60.0 - 1)
        router.wait(rids, timeout=300)
        outs = [router.result(r) for r in rids]
        summ = router.summary()
        assert not victim.alive and victim.closed
    assert outs == oracle(lm, prompts, rids, max_new=10)
    assert summ["replicas_killed"] == 1
    assert_no_fleet_threads()


def test_heartbeat_drain_records_the_open_loop_phase(lm, caplog, tmp_path):
    """A replica drained for missed heartbeats names the engine loop phase
    that kept it silent, and for how long it had been open: in
    ``RouterStats.drains``, in the log, and on a ``serve.router.drain``
    span. A paused loop waits inside ``serve.loop.hooks``."""
    model, params = lm
    topo = FleetTopology(n_replicas=2, heartbeat_timeout_s=60.0)
    prompts = make_prompts(model, 4, seed=5)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with Router(model, params, fleet_cfg(), topology=topo,
                    placement="least-loaded") as router:
            rids = [router.submit(p, max_new=6) for p in prompts]
            victim = router.replicas[0]
            deadline = time.monotonic() + 120
            busy = False
            while not busy and time.monotonic() < deadline:
                victim.engine.pause()
                with victim.engine._lock:
                    busy = bool(victim.engine._live)
                if not busy:
                    victim.engine.resume()
                    time.sleep(0.005)
            assert busy, "victim never picked up work"
            # the loop reaches its pause wait, inside the hooks phase
            while (victim.engine.phase or ("",))[0] != "serve.loop.hooks":
                assert time.monotonic() < deadline
                time.sleep(0.005)
            time.sleep(0.05)
            router.heartbeat.beat(victim.name,
                                  now=time.monotonic() - 2 * 60.0 - 1)
            router.wait(rids, timeout=300)
            summ = router.summary()
    finally:
        jax.profiler.stop_trace()
    assert len(summ["drains"]) == 1
    drain = summ["drains"][0]
    assert drain["replica"] == victim.name and drain["cause"] == "heartbeat"
    assert drain["phase"] == "serve.loop.hooks"
    assert drain["phase_s"] >= 0.05
    assert any("serve.loop.hooks" in r.getMessage() and victim.name
               in r.getMessage() for r in caplog.records)
    from jax.profiler import ProfileData
    xplane = next(tmp_path.rglob("*.xplane.pb"))
    data = ProfileData.from_serialized_xspace(xplane.read_bytes())
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert "serve.router.drain" in names
    assert_no_fleet_threads()


def test_a_crash_with_no_survivor_raises_the_crash(lm, monkeypatch):
    """The only replica crashes: its drain has nowhere to ship, and the
    router raises the crash itself, with the cause in its drain record."""
    model, params = lm

    def broken_compile(self, fn, args):
        raise ValueError("program failed to build")
    monkeypatch.setattr(Engine, "_compile", broken_compile)
    topo = FleetTopology(n_replicas=1, heartbeat_timeout_s=60.0)
    prompts = make_prompts(model, 2, seed=6)
    with Router(model, params, fleet_cfg(), topology=topo) as router:
        rids = [router.submit(p, max_new=4) for p in prompts]
        with pytest.raises(ValueError, match="failed to build"):
            router.wait(rids, timeout=120)
        drains = router.summary()["drains"]
    assert len(drains) == 1 and "failed to build" in drains[0]["cause"]
    assert drains[0]["phase"] is None
    assert_no_fleet_threads()


def test_compile_longer_than_heartbeat_timeout_drains_nothing(
        lm, monkeypatch):
    """A full-size compile silences a replica's run loop for seconds. The
    engine announces each compile, so a first compile three times the
    heartbeat timeout is not taken for a wedge: nothing drains and the
    tokens stay oracle-exact."""
    model, params = lm
    timeout = 1.0
    compile_ = Engine._compile
    slowed: set = set()

    def slow_first_compile(self, fn, args):
        if self.name not in slowed:
            slowed.add(self.name)
            time.sleep(3 * timeout)
        return compile_(self, fn, args)

    monkeypatch.setattr(Engine, "_compile", slow_first_compile)
    topo = FleetTopology(n_replicas=2, heartbeat_timeout_s=timeout)
    prompts = make_prompts(model, 4, seed=4)
    with Router(model, params, fleet_cfg(), topology=topo,
                placement="least-loaded") as router:
        rids = [router.submit(p, max_new=4) for p in prompts]
        router.wait(rids, timeout=300)
        outs = [router.result(r) for r in rids]
        summ = router.summary()
    assert slowed == {"replica-0", "replica-1"}
    assert summ["replicas_killed"] == 0 and summ["reprefills"] == 0
    assert outs == oracle(lm, prompts, rids, max_new=4)
    assert_no_fleet_threads()


# --------------------------------------------------- warm migration, direct
def _capture_warm_ticket(engine, deadline_s=120.0):
    """Run ``engine`` on a thread and pause it the moment a swapped
    request's full block set is quiescent, then detach that request as a
    warm ticket. Deterministic capture: pausing freezes the scheduler so
    the observed SWAPPED state cannot be readmitted under us."""
    err = []

    def _run():
        try:
            engine.run()
        except ReplicaKilled:
            pass
        except BaseException as e:   # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=_run)
    t.start()
    ticket = None
    deadline = time.monotonic() + deadline_s
    try:
        while ticket is None and time.monotonic() < deadline:
            engine.pause()
            ticket = engine.export_one_swapped()
            if ticket is None:
                engine.resume()
                time.sleep(0.002)
    finally:
        engine.resume()
    assert not err, err
    assert ticket is not None, "no swapped request became exportable"
    return ticket, t


def test_warm_ticket_ships_bit_exact_and_resumes(lm):
    """Engine-level warm path, deterministically: capture a swapped
    request off a busy single-slot engine, serialize → wire-decode →
    import on a second replica, and the migrated request (and everything
    that stayed behind) completes token-exact. The decoded payload is
    byte-identical to the exported one."""
    model, params = lm
    cfg = fleet_cfg(batch_buckets=(1,))
    a = Engine(model, params, cfg, name="src")
    b = Engine(model, params, cfg, name="dst")
    prompts = make_prompts(model, 3, seed=4)
    rids = [a.submit(p, max_new=10, rid=100 + i)
            for i, p in enumerate(prompts)]
    ticket, worker = _capture_warm_ticket(a)
    assert ticket.warm and ticket.rid in rids
    blob = encode_ticket(ticket)
    wire = decode_ticket(blob)
    assert wire.rid == ticket.rid and wire.out == ticket.out
    assert len(wire.blocks) == len(ticket.blocks)
    for got, want in zip(wire.blocks, ticket.blocks):
        assert set(got) == set(want)
        for k in want:
            assert got[k].tobytes() == np.ascontiguousarray(
                want[k]).tobytes()
    b.import_migration(wire)
    assert b.stats.migrations_in == 1 and a.stats.migrations_out == 1
    worker.join(timeout=300)
    assert not worker.is_alive()
    b.run()
    outs = {}
    for eng in (a, b):
        for rid, req in eng.reqs.items():
            if rid in rids:
                outs[rid] = list(req.out)
    want = oracle(lm, prompts, rids, max_new=10)
    assert [outs[r] for r in rids] == want
    a.close()
    b.close()


def test_import_refusal_is_all_or_nothing(lm):
    """A ticket the destination cannot validate or fund leaves *nothing*
    behind: no request record, no host bytes, no lease charge — the §12
    invariants hold as if the import never happened."""
    from repro.core.pool import HostPool
    model, params = lm
    cfg = fleet_cfg(batch_buckets=(1,))
    a = Engine(model, params, cfg, name="src")
    prompts = make_prompts(model, 3, seed=5)
    rids = [a.submit(p, max_new=10, rid=200 + i)
            for i, p in enumerate(prompts)]
    ticket, worker = _capture_warm_ticket(a)
    a.hard_kill()
    worker.join(timeout=300)

    # wrong block geometry → refused before any state lands
    b = Engine(model, params, fleet_cfg(block_size=32), name="dst-geom")
    with pytest.raises(MigrationRefused, match="block_size"):
        b.import_migration(ticket)
    assert ticket.rid not in b.reqs
    b.close()

    # a pool too small to fund the set → refused with every charge rolled
    # back and zero bytes resident
    pool = HostPool(1024)
    c = Engine(model, params, fleet_cfg(), pool=pool, name="dst-poor")
    with pytest.raises(MigrationRefused, match="cannot reserve"):
        c.import_migration(ticket)
    assert ticket.rid not in c.reqs
    assert pool.used_bytes == 0 and pool.drained
    assert c.host.peek_offload((ticket.rid, 0)) is None
    c.close()

    # cold tickets are never importable — the contract is resubmission
    cold = MigrationTicket(rid=1, prompt=[1, 2], out=[3], max_new=4,
                           pos=2, last=3, block_size=16)
    d = Engine(model, params, fleet_cfg(), name="dst-cold")
    with pytest.raises(MigrationRefused, match="cold"):
        d.import_migration(cold)
    d.close()
    a.close()


def test_rebalance_moves_a_swapped_request(lm):
    """Live (no-fault) migration: with one replica saturated and one idle,
    ``rebalance_once`` detaches a swapped request over the NIC and the
    burst still completes token-exact."""
    model, params = lm
    topo = FleetTopology(n_replicas=2, heartbeat_timeout_s=60.0)
    prompts = make_prompts(model, 6, seed=6)
    with Router(model, params, fleet_cfg(batch_buckets=(1,)),
                topology=topo, placement="least-loaded") as router:
        rids = [router.submit(p, max_new=12) for p in prompts]
        moved = False
        deadline = time.monotonic() + 120
        while not moved and time.monotonic() < deadline:
            moved = router.rebalance_once()
            if not moved:
                time.sleep(0.002)
            if all(router.done(r) for r in rids):
                break
        router.wait(rids, timeout=300)
        outs = [router.result(r) for r in rids]
        summ = router.summary()
    assert outs == oracle(lm, prompts, rids, max_new=12)
    if moved:     # a move is near-certain under (1,)-bucket saturation,
        #           but completion can win the race; exactness never waits
        assert summ["migrations"] + summ["reprefills"] >= 1
    assert_no_fleet_threads()


# ------------------------------------------------------------- unit pieces
def test_codec_rejects_corruption():
    t = MigrationTicket(
        rid=3, prompt=[1, 2, 3], out=[4], max_new=8, pos=4, last=4,
        block_size=4, blocks=[{"k": np.arange(8, dtype=np.float32)
                               .reshape(2, 4)}])
    blob = encode_ticket(t)
    assert decode_ticket(blob).blocks[0]["k"].dtype == np.float32
    with pytest.raises(ValueError, match="magic"):
        decode_ticket(b"XXXX" + blob[4:])
    with pytest.raises(ValueError, match="torn"):
        decode_ticket(blob[:-3])
    with pytest.raises(ValueError, match="trailing"):
        decode_ticket(blob + b"\x00")


def test_placement_policies():
    class _Eng:
        def __init__(self, n, kv):
            self._n, self._kv = n, kv

        def load(self):
            return self._n, self._kv

    class _Rep:
        def __init__(self, i, n, kv):
            self.index, self.engine = i, _Eng(n, kv)

    reps = [_Rep(0, 3, 10), _Rep(1, 1, 99), _Rep(2, 1, 5)]
    assert get_placement("least-loaded").pick(reps).index == 1  # tie → index
    assert get_placement("join-shortest-kv").pick(reps).index == 2
    rng_picks = {get_placement("random", seed=s).pick(reps).index
                 for s in range(16)}
    assert len(rng_picks) > 1                   # seeded but not degenerate
    assert set(PLACEMENT_POLICY_NAMES) == {"least-loaded",
                                           "join-shortest-kv", "random"}
    with pytest.raises(ValueError, match="unknown placement"):
        get_placement("nope")


def test_fleet_topology_validation():
    topo = make_fleet_topology(3, name_prefix="r")
    assert topo.replica_names == ("r-0", "r-1", "r-2")
    with pytest.raises(ValueError):
        FleetTopology(n_replicas=0)


# --------------------------------------------------------------- slow lane
@pytest.mark.slow
def test_fuzz_chaos_kill_instants(lm):
    """Hypothesis lane (nightly: ``-m slow``, scaled by ``FUZZ_EXAMPLES``):
    random placement policy × kill instant × burst shape, every run
    token-exact with no leaked threads."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings, strategies as st

    max_examples = max(2, int(os.environ.get("FUZZ_EXAMPLES", "25")) // 10)

    @settings(max_examples=max_examples, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.function_scoped_fixture])
    # kill_step stays below max_new: a victim holding a single request
    # completes it in exactly max_new decode steps, so a later instant
    # could let the run finish unkilled
    @given(placement=st.sampled_from(PLACEMENT_POLICY_NAMES),
           kill_step=st.integers(1, 9),
           prompt_seed=st.integers(0, 2**16),
           kill_index=st.integers(0, 2))
    def inner(placement, kill_step, prompt_seed, kill_index):
        run_chaos(lm, placement=placement, kill_step=kill_step,
                  prompt_seed=prompt_seed, n_prompts=7, max_new=10,
                  kill_index=kill_index)

    inner()
