"""Schedule-fuzz differential harness (the paper's §7 claims as a
cross-executor equivalence relation).

For random TASKGRAPHs × all four dispatch policies × random host/disk
capacities, three independent executions of every buildable plan must
agree **byte-exactly**:

* the *in-memory oracle* — direct dataflow evaluation, no memory plan;
* a *simulator replay* — the discrete-event simulator picks a schedule
  under jittered hardware, and that exact schedule (``SimResult.start_at``)
  is replayed through the sequential interpreter, so the simulator's
  scheduling choices are proven execution-valid, not just priced;
* the *threaded runtime* — real threads, condition variables, real disk
  files for SPILL/LOAD plans.

Spill plans additionally run a **shared-pool lane**: the same plan over a
store leased from an arbitrated :class:`~repro.core.pool.HostPool` with a
second consumer charging a random share under a random arbitration policy
(DESIGN.md §12) — grants move, outputs must not. The nightly hypothesis
lane (``FUZZ_EXAMPLES``) sweeps these pool configurations with generated
graphs and budgets.

And ``validate()`` must accept exactly the schedules the executors can
run: every buildable plan validates under the budgets it was compiled
for, any budget below the replayed peak is rejected (``RaceError``), and
an infeasible three-level footprint is rejected at *compile* time
(``MemgraphOOM``) before any executor sees it.

Two lanes share one checker and one generator (``helpers.py``):

* the **fast lane** (no extra deps, pinned seeds) runs in CI on every
  push;
* the **slow lane** is hypothesis-driven (``-m slow``, nightly CI);
  ``FUZZ_EXAMPLES`` scales the example count.
"""
import os
import random as pyrandom

import numpy as np
import pytest

from repro.core import (BuildConfig, HostPool, MemgraphOOM, build_memgraph,
                        certify)
from repro.core.dispatch import POLICY_NAMES
from repro.core.memgraph import DepKind, RaceError
from repro.core.runtime import TurnipRuntime, eval_taskgraph, run_in_order
from repro.core.simulate import HardwareModel, simulate

from helpers import confirm_hazard, graph_inputs, random_taskgraph

UNITS = dict(size_fn=lambda v: 1)
ARB_POLICIES = ("static", "demand", "priority")

# capacity draw spaces: None = unbounded tier; small ints force real
# spill/load traffic; 0 disk makes any spill infeasible (must reject)
HOST_CAPS = (None, 1, 2, 3)
DISK_CAPS = (None, 0, 2, 4, 50)


def _assert_equal(out, ref, what):
    assert set(out) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(out[k], ref[k],
                                      err_msg=f"{what}: output {k}")


def check_case(tg, seed: int, host_cap, disk_cap, *,
               policies=POLICY_NAMES) -> str:
    """One fuzz case; returns 'oom' | 'host' | 'disk' for coverage stats."""
    cfg = BuildConfig(capacity=3, host_capacity=host_cap,
                      disk_capacity=disk_cap, rng_seed=seed, **UNITS)
    try:
        res = build_memgraph(tg, cfg)
    except MemgraphOOM as e:
        # the compile-time feasibility check must say *which* tier cannot
        # fit — a rejected program needs an actionable error
        assert any(t in str(e) for t in ("device", "host tier", "disk tier"))
        return "oom"
    mg = res.memgraph

    # validate() accepts what the executors are about to run...
    mg.validate(check_races=True, host_capacity=host_cap,
                disk_capacity=disk_cap)
    prof = mg.host_tier_profile()
    # ...and rejects any budget below the schedule's replayed peaks: the
    # acceptance set equals the runnable set, in both directions
    if host_cap is not None and prof["peak_units"] > 0:
        with pytest.raises(RaceError, match="host-tier budget"):
            mg.validate(check_races=False,
                        host_capacity=prof["peak_units"] - 1)
    if prof["peak_disk_units"] > 0:
        with pytest.raises(RaceError, match="disk-tier budget"):
            mg.validate(check_races=False,
                        disk_capacity=prof["peak_disk_units"] - 1)

    # the static certifier (DESIGN.md §13) must prove the plan clean for
    # ALL legal orders, not just the ones sampled below — and its
    # worst-case occupancy bounds must dominate the single-order replay
    cert = certify(mg, host_capacity=host_cap, disk_capacity=disk_cap)
    assert cert.ok, f"built plan failed certification:\n{cert.summary()}"
    assert cert.worst_host_units >= prof["peak_units"]
    assert cert.worst_disk_units >= prof["peak_disk_units"]

    inputs = graph_inputs(tg, seed)
    ref = eval_taskgraph(tg, inputs)          # the in-memory oracle
    hw = HardwareModel(transfer_jitter=0.5, compute_jitter=0.2, seed=seed)
    for policy in policies:
        # simulator replay: execute exactly the schedule the simulator
        # chose (ties broken deterministically by mid)
        sim = simulate(mg, hw, mode="nondet", policy=policy)
        order = mg.topo_order(key=lambda m: (sim.start_at[m], m))
        _assert_equal(run_in_order(tg, res, inputs, order), ref,
                      f"sim-replay/{policy}")
        # threaded runtime, event-driven nondeterministic dispatch
        rr = TurnipRuntime(tg, res, mode="nondet", policy=policy,
                           seed=seed).run(inputs)
        _assert_equal(rr.outputs, ref, f"threaded/{policy}")
    # the head-of-line issue-order ablation on one policy (cost-bounded)
    rr = TurnipRuntime(tg, res, mode="fixed", policy="fixed",
                       seed=seed).run(inputs)
    _assert_equal(rr.outputs, ref, "threaded/fixed-mode")

    # compiled lane (DESIGN.md §15): the same plan lowered to a
    # straight-line CompiledPlan — static regions run with zero dispatch,
    # nondet regions hand off to the interpreter at seam vertices — must
    # reproduce the oracle byte-exactly under every policy, and every
    # vertex must be accounted to exactly one executor
    for policy in policies:
        rr = TurnipRuntime(tg, res, mode="nondet", policy=policy,
                           seed=seed, exec_backend="compiled").run(inputs)
        _assert_equal(rr.outputs, ref, f"compiled/{policy}")
        assert rr.n_compiled + rr.n_interpreted == len(mg.vertices)
        assert rr.n_inline + rr.n_threaded == rr.n_interpreted

    # forced-backend lane (DESIGN.md §17): the same compiled plan with
    # every seam forced onto ONE backend — the thread-free inline
    # executor and the threaded fleet — must stay byte-exact under every
    # policy, and the counters must show the forcing actually happened
    # (inline-forced runs spin up zero seam threads).
    for backend in ("inline", "threaded"):
        for policy in policies:
            rr = TurnipRuntime(tg, res, mode="nondet", policy=policy,
                               seed=seed, exec_backend="compiled",
                               seam_backend=backend).run(inputs)
            _assert_equal(rr.outputs, ref, f"compiled/{backend}/{policy}")
            assert rr.n_inline + rr.n_threaded == rr.n_interpreted
            if backend == "inline":
                assert rr.n_threaded == 0
            else:
                assert rr.n_inline == 0

    # shared-pool lane (DESIGN.md §12): the same plan over a store whose
    # host arena is a lease of an arbitrated HostPool, with a second
    # consumer charging a random share under a random arbitration policy.
    # Arbitration moves grants and fires revocations; it must never move
    # bytes the plan depends on — outputs stay byte-exact, and the lease
    # drains once the runtime releases its store.
    if host_cap is not None and res.n_spills:
        rngp = pyrandom.Random(seed * 31 + 7)
        pool = HostPool(1 << 20, policy=rngp.choice(ARB_POLICIES))
        mem_lease = pool.lease("memgraph", min_bytes=rngp.choice(
            (0, 1 << 16)), weight=1.0, priority=1)
        other = pool.lease("kv", weight=rngp.random() * 4 + 0.1, priority=2)
        other.try_charge(rngp.randrange(1 << 19))      # the random split
        for policy in ("random", "critical-path"):
            rr = TurnipRuntime(tg, res, mode="nondet", policy=policy,
                               seed=seed, host_lease=mem_lease).run(inputs)
            _assert_equal(rr.outputs, ref, f"pooled/{policy}")
            assert pool.used_bytes == other.used, \
                "runtime store release did not drain its lease"
        assert mem_lease.peak > 0          # the lane really accounted bytes
        assert pool.peak_bytes <= pool.capacity + mem_lease.peak
    return "disk" if res.n_loads else "host"


# ------------------------------------------------------------- fast lane
def test_fuzz_seeded_differential():
    """Pinned-seed sweep (CI fast lane): the sweep must exercise real
    disk-tier plans, at least one compile-time rejection, and every
    dispatch policy — all byte-exact."""
    outcomes = {"oom": 0, "host": 0, "disk": 0}
    for seed in range(14):
        rng = pyrandom.Random(1000 + seed)
        tg = random_taskgraph(rng)
        host_cap = rng.choice(HOST_CAPS)
        disk_cap = rng.choice(DISK_CAPS) if host_cap is not None else None
        outcomes[check_case(tg, seed, host_cap, disk_cap)] += 1
    assert outcomes["disk"] >= 3, outcomes    # disk tier really exercised
    assert outcomes["oom"] >= 1, outcomes     # rejection path exercised


def test_certifier_counterexamples_feed_the_harness():
    """The loop the certifier closes (DESIGN.md §13): seed a hazard into a
    built plan by deleting one safe-overwrite MEM edge, and the witness
    schedule the certifier emits must be a *real* counterexample — the
    harness replays it through the sequential interpreter and watches it
    raise or diverge from the oracle."""
    n_confirmed = 0
    for seed in range(8):
        rng = pyrandom.Random(1000 + seed)
        tg = random_taskgraph(rng)
        try:
            res = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=2,
                                                 rng_seed=seed, **UNITS))
        except MemgraphOOM:
            continue
        mg = res.memgraph
        mem_edges = [(u, v) for u in mg.vertices for v, k in
                     mg.succs[u].items() if k == DepKind.MEM]
        for u, v in mem_edges:
            mg.remove_dep(u, v)
            cert = certify(mg, host_capacity=2)
            for h in cert.hazards:
                if not h.confirmable:
                    continue
                try:
                    confirm_hazard(tg, res, h, seed=seed)
                except AssertionError:
                    continue      # statically real but value-coincident
                n_confirmed += 1
                break
            mg.add_dep(u, v, DepKind.MEM)
            if n_confirmed >= 3:
                return
    assert n_confirmed >= 3, "edge-deletion sweep never produced a " \
        "confirmable hazard — the certifier or the generator regressed"


def test_disk_budget_rejection_is_exact():
    """A plan whose spilled working set needs N disk units builds under a
    budget of N, and is rejected under N-1 — the feasibility check is
    tight, not merely conservative."""
    from helpers import fig3_taskgraph
    tg = fig3_taskgraph()
    res = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                         **UNITS))
    need = res.peak_disk
    assert need > 0
    ok = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                        disk_capacity=need, **UNITS))
    ok.memgraph.validate(host_capacity=1, disk_capacity=need)
    with pytest.raises(MemgraphOOM, match="disk tier"):
        build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                       disk_capacity=need - 1, **UNITS))


def test_prefetch_plans_profile_like_reactive_plans():
    """Prefetch moves LOADs earlier in the schedule; it must never move
    the budgets: hoisted plans still validate under the same host/disk
    capacities, and hide real bytes."""
    n_hoisted = 0
    for seed in range(10):
        tg = random_taskgraph(pyrandom.Random(2000 + seed))
        try:
            on = build_memgraph(tg, BuildConfig(
                capacity=3, host_capacity=1 + seed % 3, **UNITS))
            off = build_memgraph(tg, BuildConfig(
                capacity=3, host_capacity=1 + seed % 3,
                prefetch_distance=0, **UNITS))
        except MemgraphOOM:
            continue
        assert off.n_prefetches == 0
        on.memgraph.validate(check_races=True,
                             host_capacity=1 + seed % 3)
        if on.n_prefetches:
            n_hoisted += 1
            assert on.stall_bytes_hidden > 0
            prof = on.memgraph.host_tier_profile()
            assert prof["n_prefetches"] == on.n_prefetches
    assert n_hoisted >= 2      # the sweep must hit real prefetch plans


def test_compiled_seams_exercised_on_unbounded_host_plans():
    """An unbounded-host plan opens with many INPUT streams racing on the
    h2d engine — the paper's legitimately nondeterministic core. The
    compiled backend must mark those as seam regions (interpreted), run
    the rest straight-line, and still match the oracle."""
    from helpers import fig3_taskgraph
    tg = fig3_taskgraph()
    res = build_memgraph(tg, BuildConfig(capacity=3, rng_seed=0, **UNITS))
    inputs = graph_inputs(tg, 0)
    ref = eval_taskgraph(tg, inputs)
    for policy in POLICY_NAMES:
        rr = TurnipRuntime(tg, res, mode="nondet", policy=policy, seed=0,
                           exec_backend="compiled").run(inputs)
        _assert_equal(rr.outputs, ref, f"compiled-seams/{policy}")
        assert rr.n_interpreted > 0, "no seam region was interpreted"
        assert rr.n_compiled > 0, "nothing ran straight-line"


# ---------------------------------------------- migration byte-exactness
# The fleet's inter-replica wire (serve/router.py) reuses the disk tier's
# spill.log framed-record format. This lane proves the codec is a bit-exact
# round trip over adversarial KV payloads — every dtype/shape the cache
# families produce, including blocks whose bytes are resident on the DISK
# tier at export time (read back through the spill.log frame, then framed
# again for the wire).

_KV_DTYPES = ("float32", "float16", "bfloat16", "int8", "int32")


def _random_kv_ticket(rng, *, rid):
    """A migration ticket over a randomized but internally consistent leaf
    spec: every block carries the same leaves/shapes/dtypes, like a real
    ``PagedKVCache.leaf_spec`` contract."""
    from repro.serve import MigrationTicket
    import jax.numpy as jnp
    block = rng.choice((2, 4, 8))
    spec = {}
    for j in range(rng.randint(1, 4)):
        shape = (rng.randint(1, 3), block) + tuple(
            rng.randint(1, 5) for _ in range(rng.randint(0, 2)))
        spec[f"leaf{j}"] = (shape, rng.choice(_KV_DTYPES))
    np_rng = np.random.default_rng(rng.randrange(2**31))

    def draw(shape, dtype):
        raw = np_rng.integers(-120, 120, size=shape)
        if dtype == "bfloat16":       # not a numpy dtype: go through jax
            return np.asarray(jnp.asarray(raw, dtype=jnp.bfloat16))
        return raw.astype(dtype)

    n_blocks = rng.randint(1, 5)
    blocks = [{k: draw(shape, dt) for k, (shape, dt) in spec.items()}
              for _ in range(n_blocks)]
    out = [rng.randrange(100) for _ in range(rng.randint(0, 6))]
    return MigrationTicket(
        rid=rid, prompt=[rng.randrange(100) for _ in range(rng.randint(1, 9))],
        out=out, max_new=len(out) + rng.randint(1, 8),
        pos=n_blocks * block, last=out[-1] if out else 0,
        block_size=block, t_submit=0.125, t_first=0.25, blocks=blocks)


def _assert_ticket_bit_exact(got, want):
    from repro.serve import MigrationTicket
    assert isinstance(got, MigrationTicket)
    for f in ("rid", "prompt", "out", "max_new", "pos", "last",
              "block_size", "t_submit", "t_first"):
        assert getattr(got, f) == getattr(want, f), f
    assert len(got.blocks) == len(want.blocks)
    for g, w in zip(got.blocks, want.blocks):
        assert set(g) == set(w)
        for k in w:
            a, b = g[k], np.ascontiguousarray(w[k])
            assert str(a.dtype) == str(b.dtype) and a.shape == b.shape
            assert a.tobytes() == b.tobytes(), f"leaf {k} bytes diverged"


def test_migration_codec_roundtrip_bit_exact():
    """Pinned-seed sweep: serialize → decode restores every KV block
    byte-identical across the cache dtypes (incl. bfloat16/int8 scales)."""
    from repro.serve import decode_ticket, encode_ticket
    for seed in range(24):
        rng = pyrandom.Random(4000 + seed)
        want = _random_kv_ticket(rng, rid=seed)
        _assert_ticket_bit_exact(decode_ticket(encode_ticket(want)), want)
    # cold tickets (no payload) survive the wire too
    from repro.serve import MigrationTicket
    cold = MigrationTicket(rid=9, prompt=[1], out=[2, 3], max_new=5, pos=0,
                           last=3, block_size=4)
    got = decode_ticket(encode_ticket(cold))
    assert got.blocks is None and got.out == [2, 3]


def test_migration_roundtrip_through_disk_tier():
    """The ship-from-disk path: KV blocks forced down to the disk tier
    (spill.log framed records), read back via ``peek_offload`` with no
    restaging, and shipped — the decoded payload must match the original
    arrays bit-exactly even though the bytes crossed the frame twice."""
    from repro.core.stores import TieredStore
    from repro.serve import decode_ticket, encode_ticket
    for seed in range(6):
        rng = pyrandom.Random(5000 + seed)
        want = _random_kv_ticket(rng, rid=seed)
        store = TieredStore({}, host_capacity=1, auto_spill=True)
        try:
            originals = [{k: np.ascontiguousarray(v).copy()
                          for k, v in blk.items()}
                         for blk in want.blocks]
            for blk_i, blk in enumerate(want.blocks):
                store.put_offload((want.rid, blk_i), blk)
                store.spill((want.rid, blk_i))    # force disk residency
            # every block's bytes went through spill.log and left the host
            assert store.disk.write_bytes > 0
            assert all(store.tier_of((want.rid, b)) == "disk"
                       for b in range(len(want.blocks)))
            # the disk tier reads extended dtypes (bfloat16) back as
            # themselves, so the peeked blocks ship as they are
            peeked = [store.peek_offload((want.rid, b))
                      for b in range(len(originals))]
            for got_blk, orig in zip(peeked, originals):
                assert {k: v.dtype for k, v in got_blk.items()} == \
                    {k: v.dtype for k, v in orig.items()}
            shipped = dataclasses_replace_blocks(want, peeked)
            assert all(b is not None for b in shipped.blocks)
            got = decode_ticket(encode_ticket(shipped))
            shipped_ref = dataclasses_replace_blocks(want, originals)
            _assert_ticket_bit_exact(got, shipped_ref)
        finally:
            store.close()


def dataclasses_replace_blocks(t, blocks):
    import dataclasses as _dc
    return _dc.replace(t, blocks=blocks)


# ------------------------------------------------------------- slow lane
@pytest.mark.slow
def test_fuzz_hypothesis_differential():
    """Hypothesis-driven lane (nightly CI: ``-m slow`` with a larger
    ``FUZZ_EXAMPLES``): same checker, generated graphs and budgets."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings, strategies as st
    from helpers import taskgraphs

    max_examples = int(os.environ.get("FUZZ_EXAMPLES", "25"))

    @settings(max_examples=max_examples, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(tg=taskgraphs(), seed=st.integers(0, 2**16),
           host_cap=st.sampled_from(HOST_CAPS),
           disk_cap=st.sampled_from(DISK_CAPS))
    def inner(tg, seed, host_cap, disk_cap):
        if host_cap is None:
            disk_cap = None       # an unbounded host never spills to disk
        check_case(tg, seed, host_cap, disk_cap,
                   policies=("random", "critical-path"))

    inner()


@pytest.mark.slow
def test_fuzz_hypothesis_migration_codec():
    """Nightly widening of the migration byte-exactness lane: generated
    leaf specs, dtypes, and disk-tier residency — serialize → ship →
    restore stays bit-exact everywhere."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import HealthCheck, given, settings, strategies as st
    from repro.core.stores import TieredStore
    from repro.serve import decode_ticket, encode_ticket

    max_examples = int(os.environ.get("FUZZ_EXAMPLES", "25"))

    @settings(max_examples=max_examples, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(seed=st.integers(0, 2**16), via_disk=st.booleans())
    def inner(seed, via_disk):
        rng = pyrandom.Random(seed)
        want = _random_kv_ticket(rng, rid=seed)
        if via_disk:
            store = TieredStore({}, host_capacity=1, auto_spill=True)
            try:
                originals = [{k: np.ascontiguousarray(v).copy()
                              for k, v in blk.items()}
                             for blk in want.blocks]
                for i, blk in enumerate(want.blocks):
                    store.put_offload((want.rid, i), blk)
                    store.spill((want.rid, i))
                shipped = dataclasses_replace_blocks(
                    want, [store.peek_offload((want.rid, b))
                           for b in range(len(originals))])
                got = decode_ticket(encode_ticket(shipped))
                _assert_ticket_bit_exact(
                    got, dataclasses_replace_blocks(want, originals))
            finally:
                store.close()
        else:
            _assert_ticket_bit_exact(decode_ticket(encode_ticket(want)),
                                     want)

    inner()
