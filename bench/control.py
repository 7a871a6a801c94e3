#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's number and the control's
on many seeds, in one process.

For each seed the cell runs as ``run.py`` runs it (a shorter window will
do: it only has to finish and check as many requests as a run does). On
the requests the check samples, it prints the program's widest and mean
gap (``token_gap_max``, ``token_gap_mean``) and the control's: the gaps of
the tokens that the reference computed from float8 inputs puts first, at
the same prompts and served tokens. The control is judged as the program
is, by ``harness.passed`` on the cell's checks with its own gaps in place
of the program's: ``control_correct`` has to come out false.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> ...
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def summary(who: str, gaps) -> dict:
    """The widest and the mean gap (the numbers the check compares), and
    beside them the share of tokens that are not the reference's first."""
    import numpy as np
    g = np.concatenate(gaps)
    return {who: float(g.max()), f"{who}_mean": float(g.mean()),
            f"{who}_departed": float((g > 0).mean())}


def main() -> int:
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control on the first this many seeds")
    args = ap.parse_args()
    cell = harness.Cell.from_benchmark(
        harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    arch = cell.config["arch"]
    work = harness.module("models", cell.config["model"])
    for i, seed in enumerate(args.seeds):
        seen = {}

        def readings(params, sample, with_control=i < args.control_seeds):
            gaps = [work.served_gaps(params, arch, p, s) for p, s in sample]
            seen.update(summary("program", gaps))
            if with_control:
                t = time.perf_counter()
                gaps = [work.control_gaps(params, arch, p, s)
                        for p, s in sample]
                seen.update(summary("control", gaps))
                seen["control_s"] = time.perf_counter() - t

        t_start = T_START if i == 0 else time.monotonic()
        result = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                  trace=False, t_start=t_start,
                                  on_sample=readings)
        if "control" in seen:
            checks = dict(result["checks"])
            for key, who in (("token_gap_max", "control"),
                             ("token_gap_mean", "control_mean")):
                checks[key] = dict(checks[key], value=seen[who])
            seen["control_correct"] = harness.passed(checks)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "checks": result["checks"],
                          "metrics": result["metrics"], **seen}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
