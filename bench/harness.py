"""One benchmark run: set up a cell, drive its traffic through the serving
front door for a window, read its metrics, and check what it served.

Everything that belongs to one cell is found by name: the configuration in
``bench/configs/<config>.json`` (its model family's weights, reference and
work functions in ``bench/models/<model>.py``), the traffic mix in
``bench/traffic/<traffic>.json`` (its loop in ``bench/loops/<loop>.py``),
every metric's reader in ``bench/metrics/<metric>.py``, and the limits of
the check in ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import pathlib
import queue
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_REQUESTS = 12     # requests the reference re-scores, at most


class NoAccelerator(RuntimeError):
    pass


def load_peaks(kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown chip is an error."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def count_entries(d: pathlib.Path) -> int:
    return sum(1 for _ in d.iterdir()) if d.is_dir() else 0


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (a name may hold dots)."""
    key = f"bench.{kind}.{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    """A cell with everything it names already loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict

    @classmethod
    def from_benchmark(cls, bench: dict, name: str) -> "Cell":
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])

        def mine(metrics):
            return [m for m in metrics if name in m.get("workloads", [name])]

        return cls(name=name, chips=w["chips"],
                   config=load_json(ROOT / cfg["file"]),
                   traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                   end_to_end=mine(bench["end_to_end"]),
                   per_layer=mine(bench["per_layer"]),
                   limits=load_json(BENCH / "limits" / f"{name}.json"))


# ---------------------------------------------------------------- records
class Recorder:
    """Stamps every emitted token from the engine's ``on_token`` hook,
    which runs under the engine lock: it only appends."""

    def __init__(self) -> None:
        # a leaf lock: taken inside the engine lock, and alone elsewhere
        self.lock = threading.Lock()
        self.tokens: dict[int, list[float]] = {}
        self.done: dict[int, float] = {}
        # (time, engine, decode steps so far, request position) per token
        self.decode: list[tuple[float, str, int, int]] = []
        self.prefill: list[tuple[float, str, int, int]] = []
        self.completions: queue.SimpleQueue = queue.SimpleQueue()

    def attach(self, engine) -> None:
        def on_token(req, _row):
            now = time.monotonic()
            rec = (now, engine.name, engine.stats.decode_steps, req.pos)
            with self.lock:
                self.tokens.setdefault(req.rid, []).append(now)
                (self.prefill if len(req.out) == 1 else self.decode).append(rec)
                done = (len(req.out) >= req.max_new
                        or req.pos >= engine.cfg.max_len)
                if done:
                    self.done[req.rid] = now
            if done:
                self.completions.put(req.rid)
        engine.on_token = on_token

    def snapshot(self) -> dict:
        """Copies of the records, safe while the engines still run."""
        with self.lock:
            return dict(tokens={k: list(v) for k, v in self.tokens.items()},
                        done=dict(self.done), decode=list(self.decode),
                        prefill=list(self.prefill))


class Driver:
    """What a loop module sees: the pool, ``submit``, the completion queue,
    the stop flag, a seeded generator for arrivals, and the clock."""

    def __init__(self, router, pool, recorder, seed: int) -> None:
        self.router = router
        self.pool = pool
        self.completions = recorder.completions
        self.stop = threading.Event()
        self.rng = np.random.default_rng([seed, 3])
        self.clock = time.monotonic
        self.sent: dict[int, tuple[object, float, float]] = {}

    def submit(self, req, due: float | None = None) -> int:
        now = time.monotonic()
        rid = self.router.submit(req.prompt, req.max_new)
        self.sent[rid] = (req, now if due is None else due, now)
        return rid


def serve_stats(router) -> dict:
    total: dict = {}
    for rep in router.replicas:
        for k, v in dataclasses.asdict(rep.engine.stats).items():
            total[k] = total.get(k, 0) + v
    return total


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    arch: dict
    work: object                   # the model module: needed-work functions
    peaks: dict
    t0: float                      # window, time.monotonic()
    t1: float
    setup_s: float
    sent: dict                     # rid -> (request, due, sent)
    tokens: dict                   # rid -> emission times
    done: dict                     # rid -> completion time
    decode: list
    prefill: list
    stats0: dict
    stats1: dict
    compiles: list                 # end times of backend compiles
    trace: object = None           # devtrace.Trace, traced runs only

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, key: str) -> float:
        return self.stats1[key] - self.stats0[key]

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1

    def output_tokens(self) -> int:
        return sum(self.in_window(t) for ts in self.tokens.values()
                   for t in ts)

    def token_gaps(self) -> list[float]:
        """Seconds between consecutive output tokens of one request, both
        inside the window; a gap still open at the close counts with its
        length so far."""
        gaps = []
        for rid, times in self.tokens.items():
            inside = [t for t in times if self.in_window(t)]
            gaps += list(np.diff(inside))
            if inside and self.done.get(rid, self.t1) >= self.t1:
                gaps.append(self.t1 - inside[-1])
        return gaps

    def first_token_waits(self) -> list[float]:
        """Each request due in the window: seconds from when it was due
        to its first token, or to the window's close where that comes
        first."""
        out = []
        for rid, (_, due, _) in self.sent.items():
            if self.in_window(due):
                first = (self.tokens.get(rid) or [self.t1])[0]
                out.append(min(first, self.t1) - due)
        return out

    def steps(self, kind: str) -> list[tuple[float, list[int]]]:
        """Decode steps or prefill calls in the window, each as (time,
        positions of its rows), grouped by engine and step count."""
        groups: dict = {}
        for t, eng, step, pos in (self.decode if kind == "decode"
                                  else self.prefill):
            if self.in_window(t):
                groups.setdefault((eng, step), [t, []])[1].append(pos)
        return [(t, lens) for t, lens in groups.values()]

    def model_flops(self) -> tuple[float, float]:
        """FLOPs the decode steps and the prefills of the window needed."""
        dec = sum(self.work.decode_work(self.arch, lens)[0]
                  for _, lens in self.steps("decode"))
        pre = sum(self.work.prefill_work(self.arch, lens)[0]
                  for _, lens in self.steps("prefill"))
        return dec, pre

    def program(self, prefix: str) -> tuple[float, int]:
        """Device seconds and executions of the programs whose name starts
        with ``prefix``, in the traced window."""
        secs, n = 0.0, 0
        for name, (s, c) in self.trace.program_time().items():
            if name.startswith(prefix):
                secs, n = secs + s, n + c
        return secs, n

    def roofline(self, kind: str, prefix: str) -> float | None:
        """Least time the window's ``kind`` work needs on this chip, over
        the device time of its program, in percent."""
        if self.trace is None:
            return None
        dev_s, n = self.program(prefix)
        steps = self.steps(kind)
        if not n or not steps:
            return None
        fn = (self.work.decode_work if kind == "decode"
              else self.work.prefill_work)
        need = 0.0
        for _, lens in steps:
            flops, nbytes = fn(self.arch, lens)
            need += max(flops / self.peaks["bf16_flops_per_s"],
                        nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * need / dev_s


def percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, float), q))


# ------------------------------------------------------------------ set-up
def build(cell: Cell, seed: int):
    """Weights, program, router. Returns (router, model, params, work)."""
    import jax
    from repro.configs.base import ArchConfig
    from repro.launch.mesh import FleetTopology
    from repro.models import build_model
    from repro.serve import Router, ServeConfig

    cfg = cell.config
    arch = cfg["arch"]
    work = module("models", cfg["model"])
    params = work.make_params(arch, seed, device=jax.devices()[0])
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    model = build_model(ArchConfig(
        name=cfg["name"], **{k: v for k, v in arch.items() if k in fields}))
    serve = dict(cfg["serve"])
    blocks = serve.pop("host_kv_blocks", None)
    block = jax.eval_shape(lambda: model.init_cache(1, serve["block_size"]))
    block_nbytes = sum(a.size * a.dtype.itemsize for a in block.values())
    for key in ("h2d_bw", "d2h_bw", "disk_bw"):
        if key in serve and serve[key] is None:
            serve[key] = float("inf")
    serve["batch_buckets"] = tuple(serve["batch_buckets"])
    scfg = ServeConfig(**serve, seed=work.seed32(seed),
                       host_kv_bytes=None if blocks is None
                       else blocks * block_nbytes)
    router = Router(model, params, scfg, topology=FleetTopology(
        n_replicas=cell.chips, **cfg.get("fleet", {})))
    return router, model, params, work


def warm(router, cell: Cell, seed: int, vocab: int) -> int:
    """Serve the cell's own shapes once before the window: a decode at the
    full batch bucket, then a prefill at every block-aligned prompt length
    the mix can send for each admitted-batch size it lists. Requests go
    through the front door; pausing each engine while a batch is submitted
    makes it admit the batch together. Returns the requests served."""
    from bench.traffic import prompt_lengths
    block = cell.config["serve"]["block_size"]
    top = max(cell.config["serve"]["batch_buckets"])
    lo, hi = prompt_lengths(cell.traffic)
    lengths = range(-(-lo // block) * block, -(-hi // block) * block + 1,
                    block)
    rng = np.random.default_rng([seed, 4])
    engines = [rep.engine for rep in router.replicas]
    batches = [(top, lengths[0], 3)] + [
        (k, s, 1) for s in lengths for k in cell.traffic["warm_batches"]]
    for k, s, max_new in batches:
        for eng in engines:
            eng.pause()
        rids = [router.submit(rng.integers(0, vocab, s).tolist(), max_new)
                for _ in range(k * len(engines))]
        for eng in engines:
            eng.resume()
        router.wait(rids, timeout=900.0)
    for eng in engines:
        warm_paging(eng)
    return sum(k * len(engines) for k, _, _ in batches)


def warm_paging(engine) -> None:
    """Compile the cache's eager paging programs at every shape a run can
    ask for: a resume restores any number of blocks, and reads, writes and
    drops one block or slot. The engine is idle (no live request), so what
    slot 0 holds afterwards is never read."""
    import jax
    kv = engine.kv
    with engine._lock:
        block = kv.read_block(0, 0)
        for n in range(1, kv.n_blocks + 1):
            kv.restore_slot(0, [block] * n)
        kv.write_block(0, 0, block)
        kv.drop_slot(0)
        jax.block_until_ready(kv.cache)


# ------------------------------------------------------------------- check
def collect(cell: Cell, run: Run, driver: Driver, router,
            seed: int) -> tuple[list, dict, list]:
    """The requests finished in the window, with what they served; a
    seeded sample of them, the longest among them, for the reference; and
    the finished requests whose tokens are malformed."""
    finished = sorted(rid for rid, t in run.done.items()
                      if rid in driver.sent and run.in_window(t))
    served = {rid: router.result(rid) for rid in finished}
    serve = cell.config["serve"]
    vocab = cell.config["arch"]["vocab_size"]
    bad = []
    for rid in finished:
        req = driver.sent[rid][0]
        want = min(req.max_new, serve["max_len"] - len(req.prompt) + 1)
        if (len(served[rid]) != want
                or not all(0 <= t < vocab for t in served[rid])):
            bad.append(rid)
    sample = []
    if finished:
        longest = max(finished, key=lambda r: len(driver.sent[r][0].prompt)
                      + len(served[r]))
        rng = np.random.default_rng([seed, 5])
        rest = [finished[i] for i in rng.permutation(len(finished))
                if finished[i] != longest]
        sample, n_tok = [longest], len(served[longest])
        for rid in rest:
            if (n_tok >= cell.limits["checked_tokens"]
                    or len(sample) >= CHECK_REQUESTS):
                break
            sample.append(rid)
            n_tok += len(served[rid])
    return ([(driver.sent[r][0].prompt, served[r]) for r in sample],
            {"finished": len(finished)}, bad)


def verify(cell: Cell, sample: list, bad: list, params,
           work) -> tuple[dict, dict]:
    """Re-score the sample with the plain reference. Returns (checks,
    readings)."""
    t = time.perf_counter()
    gaps = [work.served_gaps(params, cell.config["arch"], prompt, served)
            for prompt, served in sample]
    every = np.concatenate(gaps) if gaps else np.full(1, np.nan)
    n_tok = sum(len(s) for _, s in sample)
    readings = {"check_requests": len(sample), "check_tokens": n_tok,
                "check_longest_tokens": (len(sample[0][0]) + len(sample[0][1])
                                         if sample else 0),
                "reference_s": time.perf_counter() - t,
                "malformed_requests": len(bad)}
    checks = {"token_gap_max": {"value": float(every.max()),
                                "limit": cell.limits["token_gap_max"]},
              "token_gap_mean": {"value": float(every.mean()),
                                 "limit": cell.limits["token_gap_mean"]},
              "malformed_requests": {"value": len(bad), "limit": 0},
              "checked_tokens": {"value": n_tok,
                                 "limit": cell.limits["checked_tokens"],
                                 "at_least": True}}
    return checks, readings


def passed(checks: dict) -> bool:
    for c in checks.values():
        v, lim = c["value"], c["limit"]
        if v != v:                          # NaN: nothing was compared
            return False
        if c.get("at_least") and v < lim:
            return False
        if not c.get("at_least") and v > lim:
            return False
    return True


# --------------------------------------------------------------------- run
def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_chips: bool = True,
             peaks: dict | None = None, on_sample=None, on_run=None) -> dict:
    """One run. Prints readings on earlier lines and returns the result
    object (the caller prints it last). ``require_chips=False`` and
    ``peaks`` let a test drive the rest of a run on the CPU;
    ``on_sample(params, sample)`` sees the checked requests and the
    weights once the check is done, and ``on_run(run)`` the ``Run`` that
    the readers read."""
    import jax
    from bench import devtrace
    from bench.traffic import Pool

    devices = jax.devices()
    if require_chips and (devices[0].platform == "cpu"
                          or len(devices) < cell.chips):
        raise NoAccelerator(f"cell {cell.name} needs {cell.chips} "
                            f"accelerator(s); JAX sees {len(devices)} "
                            f"{devices[0].platform} device(s)")
    kind = devices[0].device_kind
    if peaks is None:
        peaks = load_peaks(kind)
    cache_dir = jax.config.jax_compilation_cache_dir
    n_cached = count_entries(pathlib.Path(cache_dir or "/nonexistent"))

    compiles: list[float] = []
    compiled: list[tuple[float, str]] = []

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles.append(time.monotonic())
            compiled.append((compiles[-1], str(kw.get("fun_name", "?"))))
    jax.monitoring.register_event_duration_secs_listener(on_event)

    t = time.monotonic()
    router, model, params, work = build(cell, seed)
    recorder = Recorder()
    for rep in router.replicas:
        recorder.attach(rep.engine)
    t_build = time.monotonic() - t
    vocab = cell.config["arch"]["vocab_size"]
    t = time.monotonic()
    n_warm = warm(router, cell, seed, vocab)
    t_sweep = time.monotonic() - t
    while not recorder.completions.empty():
        recorder.completions.get()
    pool = Pool(cell.traffic, seed, vocab)
    driver = Driver(router, pool, recorder, seed)
    loop = module("loops", cell.traffic["loop"])
    thread = threading.Thread(target=loop.drive, args=(driver, cell.traffic),
                              name="bench-load", daemon=True)
    thread.start()
    try:
        time.sleep(cell.traffic["warmup_s"])
        tdir = None
        if trace:
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            stats0 = serve_stats(router)
            t0 = time.monotonic()
            time.sleep(seconds)
            t1 = time.monotonic()
            stats1 = serve_stats(router)
        if trace:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            t_stop = time.perf_counter() - t
    finally:
        driver.stop.set()
        thread.join()
    memory = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peak = max((m.get("peak_bytes_in_use", 0) for m in memory), default=0)

    run = Run(arch=cell.config["arch"], work=work, peaks=peaks, t0=t0, t1=t1,
              setup_s=t0 - t_start, sent=dict(driver.sent),
              stats0=stats0, stats1=stats1, compiles=list(compiles),
              **recorder.snapshot())
    sample, readings, bad = collect(cell, run, driver, router, seed)
    router.close()
    # the program's state goes before the reference runs: only the
    # weights, which the benchmark made, stay on the device
    del router, model, driver, recorder
    gc.collect()
    checks, more = verify(cell, sample, bad, params, work)
    readings.update(more)
    if on_sample is not None:
        on_sample(params, sample)
    if tdir is not None:
        t = time.perf_counter()
        xplane = devtrace.find_xplane(tdir)
        readings["trace_bytes"] = os.path.getsize(xplane)
        readings["trace_stop_s"] = t_stop
        run.trace = devtrace.reduce(xplane)
        shutil.rmtree(tdir, ignore_errors=True)
        readings["trace_reduce_s"] = time.perf_counter() - t
    jax.monitoring.unregister_event_duration_listener(on_event)

    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in specs:
        value = module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    attempted = [r for r, (_, due, _) in run.sent.items() if run.in_window(due)]
    n_done = sum(run.in_window(run.done.get(r, -1.0)) for r in run.sent)
    d = run.delta
    n_now = count_entries(pathlib.Path(cache_dir or "/nonexistent"))
    print(f"compile_cache: dir {cache_dir} entries_found {n_cached} "
          f"entries_added {n_now - n_cached}")
    print(f"setup: build_s {t_build} warm_sweep_s {t_sweep} "
          f"warm_requests {n_warm} warmup_traffic_s "
          f"{cell.traffic['warmup_s']} setup_s {run.setup_s}")
    print(f"window: seconds {run.seconds} attempted {len(attempted)} "
          f"completed {n_done} output_tokens {run.output_tokens()} "
          f"decode_steps {d('decode_steps')} compiles "
          f"{sum(run.in_window(c) for c in run.compiles)}")
    print("compiled_in_window: " + (" ".join(
        n for t, n in compiled if run.in_window(t)) or "none"))
    print(f"tiers: swaps {d('swaps')} swaps_per_completed "
          f"{d('swaps') / max(n_done, 1)} offload_bytes {d('offload_bytes')} "
          f"reload_bytes {d('reload_bytes')} disk_spill_bytes "
          f"{d('disk_spill_bytes')} disk_load_bytes {d('disk_load_bytes')}")
    late = [sent - due for _, due, sent in run.sent.values()
            if run.in_window(due)]
    print(f"load: requests_sent {len(run.sent)} due_in_window {len(late)} "
          f"lateness_max_ms {1e3 * max(late, default=0.0)} lateness_p99_ms "
          f"{1e3 * percentile(late, 99) if late else 0.0}")
    print("logits_recorded: 0 requests; the window stamps token times only "
          "and the check re-scores served tokens after the window")
    print("check: " + " ".join(f"{k} {v}" for k, v in readings.items()))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": passed(checks), "attempted": len(attempted),
              "failed": readings["malformed_requests"], "metrics": metrics,
              "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        progs = sorted(run.trace.program_time().items(),
                       key=lambda kv: -kv[1][0])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, (s, _) in progs[:10]],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps(10)]}
    result["checks"] = checks
    if on_run is not None:
        on_run(run)
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.monotonic() if t_start is None else t_start
    cell = Cell.from_benchmark(load_json(ROOT / "BENCHMARK.json"),
                               args.workload)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=t_start)
    except NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        rel = ">=" if c.get("at_least") else "<="
        print(f"check {name}: {c['value']} (limit {rel} {c['limit']})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0
