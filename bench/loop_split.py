#!/usr/bin/env python3
"""Where the engine loop's time goes, from one traced run of a cell.

    python3 bench/loop_split.py --workload <name> --seed <n> --seconds <s>
                                [--slice <s>] [--out <file.json>]

Runs the cell as ``run.py --trace 1`` does, reporting its end-to-end
metrics beside the per-layer ones, and reads the engine's ``serve.*``
spans out of the same trace. It prints one JSON object (and writes it to
``--out``): the window split by run-loop phase; the device's idle time
that lies under each loop phase; the streams' spans; and the phases,
decode steps, tokens and idle time of each ``--slice`` seconds of the
window. A program without the spans gives empty splits.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

LOOP = ("serve.loop.", "serve.kv.", "serve.prefill", "serve.decode")


def spans(path: str) -> dict:
    """``serve.*`` host events by thread line: {line: [(name, start s,
    end s, stats)]}."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    lines: dict = collections.defaultdict(list)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        s = ev.start_ns * 1e-9
                        lines[(plane.name, i)].append(
                            (ev.name, s, s + ev.duration_ns * 1e-9,
                             dict(ev.stats)))
    return lines


def overlap(a: list, b: list) -> float:
    """Seconds in both of two sorted lists of disjoint (start, end)."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def split(trace, lines: dict, slice_s: float) -> dict:
    """The loop's split by phase, idle time under each phase, the streams'
    spans, and the same per slice of the window."""
    w0, w1 = trace.window
    idle = []
    for iv in trace.busy.values():
        t = w0
        for s, e in iv + [(w1, w1)]:
            if s > t:
                idle.append((t, s))
            t = max(t, e)
    idle.sort()
    n_dev = len(trace.busy) or 1
    loop, streams = [], []
    for evs in lines.values():
        is_loop = any(n.startswith("serve.loop.") for n, _, _, _ in evs)
        for name, s, e, stats in evs:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                (loop if is_loop and name.startswith(LOOP)
                 else streams).append((name, s, e, stats))
    # whole slices; a remainder shorter than half a slice joins the last
    n_slices = max(round((w1 - w0) / slice_s), 1)
    bounds = [w0 + k * slice_s for k in range(n_slices)] + [w1]

    def table(items, lo, hi):
        by: dict = collections.defaultdict(list)
        for name, s, e, _ in items:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by[name].append((s, e))
        return by

    def report(lo, hi):
        by = table(loop, lo, hi)
        gaps = [(max(s, lo), min(e, hi)) for s, e in idle
                if min(e, hi) > max(s, lo)]
        idle_s = sum(e - s for s, e in gaps) / n_dev
        phases = {n: sum(e - s for s, e in iv) for n, iv in by.items()}
        under = {n: overlap(sorted(iv), gaps) / n_dev
                 for n, iv in by.items()}
        rows = [st.get("rows", 0) for n, s, _, st in loop
                if n == "serve.decode" and lo <= s < hi]
        return {"seconds": hi - lo, "idle_s": idle_s,
                "loop_covered_s": sum(phases.values()),
                "idle_under_loop_s": sum(under.values()),
                "phases_s": dict(sorted(phases.items(),
                                        key=lambda kv: -kv[1])),
                "idle_under_s": dict(sorted(under.items(),
                                            key=lambda kv: -kv[1])),
                "decode_steps": len(rows), "decode_tokens": sum(rows)}

    out = report(w0, w1)
    st = table(streams, w0, w1)
    out["streams"] = {n: {"seconds": sum(e - s for s, e in iv),
                          "count": len(iv)} for n, iv in st.items()}
    out["slices"] = [report(lo, hi) for lo, hi in zip(bounds, bounds[1:])]
    return out


def main() -> int:
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench import devtrace, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice", type=float, default=15.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = harness.Cell.from_benchmark(
        harness.load_json(ROOT / "BENCHMARK.json"), args.workload)
    cell.per_layer = cell.per_layer + cell.end_to_end
    found = {}
    reduce = devtrace.reduce

    def reduce_and_split(path):
        trace = reduce(path)
        found.update(split(trace, spans(path), args.slice))
        return trace
    devtrace.reduce = reduce_and_split
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=True, t_start=T_START)
    report = {"workload": args.workload, "seed": args.seed,
              "metrics": {k: v["value"]
                          for k, v in result["metrics"].items()},
              "correct": result["correct"], "device": result["device"],
              "idle_gaps": result["breakdown"]["idle_gaps"], **found}
    text = json.dumps(report)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
