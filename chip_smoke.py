#!/usr/bin/env python3
"""Bring-up smoke run: serve Qwen2.5-3B with KV offload on a TPU.

Drives the serving path a user calls, ``repro.serve.Engine.generate``, at
the published Qwen2.5-3B widths in bfloat16, with random weights made from
``--seed``. Each engine serves twelve requests in eight batch slots, so
preemption swaps requests out to host RAM and back; the host budget holds
fewer KV bytes than are offloaded, so some blocks spill to the disk tier
and reload from it. Every token of every request is checked against the
unbatched oracle (``repro.serve.naive_generate``), teacher-forced on the
engine's tokens so positions after a swap and reload are checked too.

    python chip_smoke.py                # one chip: one Engine
    python chip_smoke.py --four-chips   # a Router: four replicas, one per
                                        # chip, twelve requests each

It exits nonzero, and prints no result, when JAX finds no TPU. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

import jax
import numpy as np

PROMPT_LENGTHS = (200, 700, 1500)   # three lengths: three oracle prefills
N_REQUESTS = 12                     # per engine; > 8 slots: swaps happen
MAX_NEW = 32
MAX_LEN = 2048
BLOCK = 32
HOST_BLOCKS = 64                    # host tier budget, in KV blocks
# how far an engine logit may sit from the oracle's, as a fraction of the
# oracle row's largest: eight bfloat16 ulps there. Rounding in the
# engine's batched, padded programs moves logits by a few ulps; a lost or
# wrong KV block moves them by far more
TOL = 2.0 ** -4
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class _CompileClock:
    """Records JAX's compile phases as wall-clock intervals, each stamped
    when it ended."""

    def __init__(self) -> None:
        self.spans: list[tuple[float, float]] = []

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            end = time.monotonic()
            self.spans.append((end - duration, end))

    def before(self, t: float) -> float:
        """Seconds before ``t`` during which at least one compile ran: the
        union of the intervals, so compiles on concurrent replica threads
        count once."""
        total, reach = 0.0, -math.inf
        for lo, hi in sorted(self.spans):
            lo, hi = max(lo, reach), min(hi, t)
            if hi > lo:
                total += hi - lo
            reach = max(reach, hi)
        return total


def serve_config(model, *, seed: int):
    """The smoke's ServeConfig: every tier in use, no simulated wire time."""
    from repro.serve import ServeConfig
    block = jax.eval_shape(lambda: model.init_cache(1, BLOCK))
    block_nbytes = sum(a.size * a.dtype.itemsize for a in block.values())
    inf = float("inf")
    return ServeConfig(
        max_len=MAX_LEN, block_size=BLOCK, batch_buckets=(1, 4, 8),
        offload=True, hot_window=BLOCK, preempt_every=8,
        host_kv_bytes=HOST_BLOCKS * block_nbytes,
        dma_latency=0.0, h2d_bw=inf, d2h_bw=inf, disk_bw=inf, seed=seed)


def make_prompts(vocab: int, seed: int, n: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, PROMPT_LENGTHS[i % len(PROMPT_LENGTHS)])
            .tolist() for i in range(n)]


def compare_with_oracle(outs, rows, oracle) -> dict:
    """Compare every position of each request with the oracle, which was
    teacher-forced on the engine's tokens, so both score the same prefix.
    No logit of the engine's row (``rows[rid][pos]``) may differ from the
    oracle's by more than ``TOL`` of the oracle row's largest logit; where
    the two greedy choices differ, the departure is listed with the
    oracle's gap between them, in the same unit. Returns the counts, the
    departures, the largest deviation and the failures."""
    matched, departures, worst, failures = 0, [], 0.0, []
    for rid, (got, (want, orows)) in enumerate(zip(outs, oracle)):
        if len(got) != len(want) or len(rows.get(rid, ())) != len(want):
            failures.append(f"request {rid}: {len(got)} tokens, "
                            f"{len(rows.get(rid, ()))} logit rows, oracle "
                            f"{len(want)}")
            continue
        matched += got == want
        for pos, (g, w, row, orow) in enumerate(
                zip(got, want, rows[rid], orows)):
            scale = float(np.abs(orow).max()) or 1.0
            dev = float(np.abs(row - orow).max()) / scale
            worst = max(worst, dev)
            if dev > TOL:
                failures.append(f"request {rid} pos {pos}: a logit departs "
                                f"by {dev} of the row's largest")
            if g != w:
                departures.append(dict(
                    rid=rid, pos=pos, oracle_token=w, token=g,
                    gap=(float(orow[w]) - float(orow[g])) / scale))
    return dict(matched=matched, departures=departures, worst=worst,
                failures=failures)


def serve_phase(cfg, *, seed: int = 0, replicas: int = 0) -> dict:
    """Serve ``N_REQUESTS`` prompts per engine of model config ``cfg`` with
    offload on and check them. ``replicas=0`` drives one :class:`Engine`
    on the first device; ``replicas=n`` a :class:`Router` over ``n``
    replicas at its default topology, replica ``i`` on device
    ``i mod len(jax.devices())``. Returns the readings;
    ``readings["failures"]`` lists every check that failed."""
    from repro.launch.mesh import FleetTopology
    from repro.models import build_model
    from repro.serve import Engine, Router, naive_generate

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    scfg = serve_config(model, seed=seed)
    prompts = make_prompts(cfg.vocab_size, seed,
                           N_REQUESTS * max(replicas, 1))
    vocab = cfg.vocab_size
    r: dict = {"model": cfg.name, "dtype": cfg.dtype,
               "param_bytes": sum(a.nbytes for a in jax.tree.leaves(params)),
               "host_kv_bytes": scfg.host_kv_bytes}
    failures: list[str] = []

    rows: dict[int, list[np.ndarray]] = {}

    def keep_row(req, row) -> None:         # engine lock held: copy only
        rows.setdefault(req.rid, []).append(
            np.array(row[:vocab], np.float32))

    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        t0 = time.perf_counter()
        if replicas:
            topo = FleetTopology(n_replicas=replicas)
            with Router(model, params, scfg, topology=topo) as router:
                for rep in router.replicas:
                    rep.engine.on_token = keep_row
                rids = [router.submit(p, MAX_NEW) for p in prompts]
                router.wait(rids, timeout=900.0)
                outs = [router.result(rid) for rid in rids]
                engines = [rep.engine for rep in router.replicas]
                if router.stats.replicas_killed or router.stats.reprefills:
                    failures.append(f"router drained replicas: "
                                    f"{router.stats}")
        else:
            with Engine(model, params, scfg) as eng:
                eng.on_token = keep_row
                outs = eng.generate(prompts, max_new=MAX_NEW)
                engines = [eng]
        r["wall_s (smoke reading, not a metric)"] = time.perf_counter() - t0
        t_first = min(q.t_first for e in engines for q in e.reqs.values())
        r["compile_s_before_first_token"] = clock.before(t_first)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)

    keys = ("decode_steps", "tokens", "swaps", "offload_bytes",
            "reload_bytes", "disk_spill_bytes", "disk_load_bytes")
    for key in keys:
        r[key] = sum(getattr(e.stats, key) for e in engines)
    for e in engines:
        if replicas:
            r[f"{e.name} tiers"] = {k: getattr(e.stats, k) for k in keys}
        for key in ("swaps", "offload_bytes", "reload_bytes",
                    "disk_load_bytes"):
            if getattr(e.stats, key) <= 0:
                failures.append(f"{e.name}: {key} is 0: a tier went unused")
    if replicas:
        placed = {}
        for e in engines:
            trees = (e.params, e.kv.cache if e.kv is not None else {})
            devs = {d for a in jax.tree.leaves(trees) for d in a.devices()}
            if len(devs) != 1:
                failures.append(f"{e.name} params and cache span {devs}")
            placed[e.name] = devs
        r["replica_devices"] = {n: sorted(map(str, d))
                                for n, d in placed.items()}
        if len(set().union(*placed.values())) != replicas:
            failures.append(f"replicas share devices: {placed}")

    for rid, toks in enumerate(outs):
        if len(toks) != MAX_NEW or not all(0 <= t < vocab for t in toks):
            failures.append(f"request {rid}: {len(toks)} tokens, range "
                            f"[{min(toks, default=None)}, "
                            f"{max(toks, default=None)}]")
    r["completed"] = f"{sum(len(t) == MAX_NEW for t in outs)}/{len(outs)}"

    oracle = [naive_generate(model, params, p, max_new=MAX_NEW,
                             max_len=MAX_LEN, rid=i, seed=seed,
                             return_logits=True, force=outs[i])
              for i, p in enumerate(prompts)]
    cmp = compare_with_oracle(outs, rows, oracle)
    r["oracle_matched"] = f"{cmp['matched']}/{len(outs)}"
    r["positions_checked"] = sum(len(t) for t, _ in oracle)
    r["logit_deviation_max"] = cmp["worst"]
    r["token_departures"] = cmp["departures"]
    failures += cmp["failures"]
    stats = [d.memory_stats() for d in jax.devices()]
    r["peak_bytes_in_use"] = [s.get("peak_bytes_in_use") if s else None
                              for s in stats][:max(replicas, 1)]
    r["failures"] = failures
    return r


def _count_entries(d: pathlib.Path) -> int:
    return sum(1 for _ in d.iterdir()) if d.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="serve through a 4-replica Router, one per chip")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU found: JAX sees {devices[0].platform} devices only",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"--four-chips needs 4 TPU devices, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = pathlib.Path(enable_compile_cache())
    n_cached = _count_entries(cache_dir)
    print(f"device_kind: {devices[0].device_kind}")
    print(f"device_count: {len(devices)}")
    print(f"compile_cache_dir: {cache_dir}")
    r = serve_phase(get_arch("qwen2.5-3b"), seed=args.seed,
                    replicas=4 if args.four_chips else 0)
    failures = r.pop("failures")
    for key, value in r.items():
        print(f"{key}: {value}")
    print(f"compile_cache_entries_added: "
          f"{_count_entries(cache_dir) - n_cached}")
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
