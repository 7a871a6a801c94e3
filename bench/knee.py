#!/usr/bin/env python3
"""One point of an open-loop cell's knee sweep: the cell at a given rate,
read for whether the engine keeps up.

The cell runs as ``run.py`` runs it, with its traffic at ``--rate`` and a
pool as large as one window sends (rate × seconds). The rate is sustained
when, at the window's close, fewer of the requests due in the window are
still unfinished than the cell has slots: beyond that, requests wait in the
engine's queue or swapped out, and the backlog grows. (The engine admits
fresh requests ahead of swapped ones and preempts running rows for them, so
an overloaded engine keeps the wait for a first token short and shows its
backlog as swapped requests.) It prints one JSON line: requests due in the
window, those unfinished and those with no first token at its close, time
to first token at a few percentiles, the batch sizes of the window's
prefills, swaps, compiles in the window and the run's check. The knee is
the highest rate of a sweep, one process a rate, that is sustained.

    python3 bench/knee.py --workload <name> --seconds <s> --seed <n> \
        --rate <r> [--warm-batches <k> ...]
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def window_load(run) -> dict:
    """What the window's requests waited: those due in it, those still
    unfinished and those with no first token at its close, and the time to
    first token at a few percentiles (a request still waiting counts with
    its wait so far)."""
    import numpy as np
    waits = run.first_token_waits()
    due = [rid for rid, (_, t, _) in run.sent.items() if run.in_window(t)]
    pct = {f"ttft_p{q}_s": float(np.percentile(waits, q)) if waits else None
           for q in (50, 80, 90, 99)}
    sizes = collections.Counter(len(lens) for _, lens in run.steps("prefill"))
    return {"due": len(due),
            "unfinished_at_close": sum(run.done.get(rid, run.t1) >= run.t1
                                       for rid in due),
            "no_first_token_at_close": sum(
                (run.tokens.get(rid) or [run.t1])[0] >= run.t1
                for rid in due),
            **pct, "prefill_batches": dict(sorted(sizes.items())),
            "swaps": run.delta("swaps"),
            "compiles_in_window": sum(map(run.in_window, run.compiles))}


def main() -> int:
    # the compile cache lives in the checkout, at a fixed path, as run.py
    # keeps it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--warm-batches", type=int, nargs="+",
                    help="admitted-batch sizes to warm (default: the mix's)")
    args = ap.parse_args()
    cell = harness.Cell.from_benchmark(
        harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    traffic = dict(cell.traffic, rate=args.rate,
                   pool=round(args.rate * args.seconds))
    if args.warm_batches:
        traffic["warm_batches"] = args.warm_batches
    seen = {}
    result = harness.run_cell(
        dataclasses.replace(cell, traffic=traffic), seed=args.seed,
        seconds=args.seconds, trace=False, t_start=T_START,
        on_run=lambda run: seen.update(run=run))
    load = window_load(seen["run"])
    slots = max(cell.config["serve"]["batch_buckets"])
    print(json.dumps({
        "rate": args.rate, "seed": args.seed, **load,
        "sustained": load["unfinished_at_close"] < slots,
        "correct": result["correct"], "metrics": result["metrics"],
        "memory_peak_bytes": result["device"]["memory_peak_bytes"],
        "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
