"""The serving engine's spans and counters (``repro.serve.spans``): every
``serve.*`` span reaches a profiler trace on the trace's clock, the
run-loop phases never overlap and add up to the loop's wall time, the
counters fill with the profiler off, and a transfer's spans carry the
request and block they move."""
import pathlib
import shutil
import sys
import tempfile
import time
import warnings

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.models import build_model
from repro.serve import Engine, PagedKVCache, ServeConfig
from repro.serve.engine import LOOP_PHASES

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import devtrace  # noqa: E402

# the run loop's phases, each the span of one ServeStats counter
LOOP_SPANS = {
    "serve.loop.hooks": "hook_time",
    "serve.loop.lock_wait": "lock_wait_time",
    "serve.loop.events": "events_time",
    "serve.kv.restore_slot": "restore_time",
    "serve.kv.drop_slot": "drop_time",
    "serve.loop.admit": "admit_time",
    "serve.prefill": "prefill_time",
    "serve.kv.scatter_prefill": "scatter_time",
    "serve.loop.schedule": "schedule_time",
    "serve.decode": "decode_time",
    "serve.decode.emit": "emit_time",
    "serve.loop.stall": "stall_time",
}
# the DMA and disk streams' spans, on their own threads, each with the
# ServeStats counters its time goes to
WIRE = ("d2h_wire_time", "h2d_wire_time", "disk_wire_time")
STREAM_SPANS = {
    "serve.d2h.copy": ("d2h_copy_time",),
    "serve.d2h.store": ("d2h_store_time",),
    "serve.h2d.copy": ("h2d_copy_time",),
    "serve.disk.spill": ("disk_io_time",),
    "serve.disk.load": ("disk_io_time",),
    "serve.disk.prefetch": ("disk_io_time",),
    "serve.dma.wire": WIRE,
}
# the annotation around each engine's run() in the traced fixture
RUN_SPAN = "test.run"

PROMPTS = [list(range(1, 25)), list(range(30, 48)), [7, 8, 9, 10, 11]]


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(get_arch("olmo-1b"))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def tiered_cfg(lm, host_blocks: int) -> ServeConfig:
    """One slot, preemption every 3 steps, a host tier of ``host_blocks``
    blocks over a disk tier, and simulated wire time on every stream: one
    block of host room makes every resume load from disk; three leave room
    to prefetch."""
    blk = PagedKVCache(lm[0], 1, 64, block_size=8).block_nbytes
    return ServeConfig(max_len=64, batch_buckets=(1,), block_size=8,
                       offload=True, hot_window=0, preempt_every=3,
                       h2d_bw=500e6, d2h_bw=500e6, disk_bw=300e6,
                       host_kv_bytes=host_blocks * blk)


def serve(lm, cfg: ServeConfig) -> tuple[Engine, float]:
    """Serve ``PROMPTS``; returns the closed engine and run()'s wall time.
    The run lies in a ``RUN_SPAN`` trace event."""
    with Engine(*lm, cfg) as eng:
        for p in PROMPTS:
            eng.submit(p, max_new=8)
        with jax.profiler.TraceAnnotation(RUN_SPAN):
            t = time.perf_counter()
            eng.run()
            return eng, time.perf_counter() - t


def events(path: str):
    """Every ``serve.*`` and ``RUN_SPAN`` host event of the trace: (thread
    line, name, start s, duration s, stats)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if (ev.name.startswith("serve.")
                            or ev.name == RUN_SPAN):
                        out.append(((plane.name, i), ev.name,
                                    ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9, dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(lm):
    """Two engines served under one profiler trace: disk loads on the
    first, prefetches on the second."""
    tdir = tempfile.mkdtemp(prefix="serve-spans-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            runs = [serve(lm, tiered_cfg(lm, n)) for n in (1, 3)]
        jax.profiler.stop_trace()
        path = devtrace.find_xplane(tdir)
        yield runs, devtrace.reduce(path), events(path)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def test_trace_holds_every_engine_span(traced):
    runs, trace, _ = traced
    names = {name for name, _, _ in trace.host}
    for name in (*LOOP_SPANS, *STREAM_SPANS):
        assert name in names, name
    assert all(eng.stats.disk_load_bytes > eng.stats.prefetch_bytes
               for eng, _ in runs[:1])
    assert runs[1][0].stats.prefetch_bytes > 0


def test_loop_phases_never_overlap(traced):
    _, _, evs = traced
    lines: dict = {}
    for line, name, s, d, _ in evs:
        if name in LOOP_SPANS:
            lines.setdefault(line, []).append((s, s + d, name))
    assert lines
    for spans in lines.values():
        spans.sort()
        for (_, e0, a), (s1, _, b) in zip(spans, spans[1:]):
            assert s1 >= e0 - 1e-6, (a, b, e0 - s1)


def test_loop_phase_spans_match_their_counters(traced):
    """Each phase's counter holds the total of its spans. A span's clock
    reads sit inside its trace event, and another thread taking the
    interpreter between the two can stretch the event by a switch
    interval, so the event totals may exceed the counters by a few."""
    runs, _, evs = traced
    slack = 4 * sys.getswitchinterval()
    for name, counter in LOOP_SPANS.items():
        traced_s = sum(d for _, n, _, d, _ in evs if n == name)
        counted = sum(getattr(eng.stats, counter) for eng, _ in runs)
        assert counted <= traced_s + 1e-4, name
        assert traced_s - counted <= 0.05 * traced_s + slack, name


@pytest.mark.parametrize("counters", sorted(set(STREAM_SPANS.values())))
def test_stream_spans_match_their_counters(traced, counters):
    """A stream's counters hold the total of the spans that fill them, as
    a loop phase's counter does."""
    runs, _, evs = traced
    names = [n for n, c in STREAM_SPANS.items() if c == counters]
    slack = 4 * sys.getswitchinterval()
    traced_s = sum(d for _, n, _, d, _ in evs if n in names)
    counted = sum(getattr(eng.stats, c) for eng, _ in runs for c in counters)
    assert 0 < counted <= traced_s + 1e-4, names
    assert traced_s - counted <= 0.05 * traced_s + slack, names


def test_loop_phases_add_up_to_the_loop_wall_time(traced):
    """In each run(), from its first phase's start to its last phase's
    end, the phases leave no gap; the counters hold the same total. The
    runs share a thread, so each is measured inside its own ``RUN_SPAN``:
    the time between two runs (one engine's shutdown, the next one's
    construction) belongs to no loop."""
    runs, _, evs = traced
    assert set(LOOP_SPANS.values()) == set(LOOP_PHASES)
    bounds = [(line, s, s + d) for line, name, s, d, _ in evs
              if name == RUN_SPAN]
    assert len(bounds) == len(runs)
    covered = wall = 0.0
    for run_line, lo, hi in bounds:
        spans = [(s, s + d) for line, name, s, d, _ in evs
                 if name in LOOP_SPANS and line == run_line
                 and lo <= s and s + d <= hi]
        wall += max(e for _, e in spans) - min(s for s, _ in spans)
        covered += sum(e - s for s, e in spans)
    assert covered == pytest.approx(wall, rel=0.05)
    counted = sum(eng.stats.loop_time for eng, _ in runs)
    assert counted == pytest.approx(covered, rel=0.05)
    assert all(eng.stats.loop_time <= run_wall for eng, run_wall in runs)


def test_counters_fill_with_the_profiler_off(lm):
    eng, _ = serve(lm, tiered_cfg(lm, 1))
    st = eng.stats
    for counter in LOOP_PHASES:
        assert getattr(st, counter) > 0, counter
    assert st.d2h_copy_time > 0 and st.d2h_copy_bytes >= st.offload_bytes > 0
    assert st.d2h_store_time > 0 and st.disk_io_time > 0
    assert st.h2d_copy_time > 0 and st.h2d_copy_bytes > 0
    assert st.h2d_staged_blocks > 0 and st.h2d_unstaged_blocks == 0
    assert st.d2h_wire_time > 0 and st.disk_wire_time > 0
    assert st.admissions == len(PROMPTS) and st.queue_time > 0
    assert st.restores >= st.resumes > 0
    assert st.swap_stall_time == pytest.approx(
        st.swapped_time + st.reloading_time)
    assert st.swapped_time > 0 and st.reloading_time > 0


def test_transfer_spans_carry_request_and_block(traced):
    _, _, evs = traced
    d2h = [stats for _, n, _, _, stats in evs
           if n in ("serve.d2h.copy", "serve.d2h.store")]
    h2d = [stats for _, n, _, _, stats in evs if n == "serve.h2d.copy"]
    assert d2h and h2d
    for stats in d2h + h2d:
        assert {"rid", "blk", "nbytes"} <= set(stats), stats
    spills = [stats for _, n, _, _, stats in evs if n == "serve.disk.spill"]
    assert spills and all(s.get("under_lock") == 1 for s in spills)
    rids = {s["rid"] for s in d2h}
    assert rids <= {0, 1, 2} and {s["rid"] for s in h2d} <= rids
    restores = [s for _, n, _, _, s in evs if n == "serve.kv.restore_slot"]
    assert restores and all(s["rid"] in rids and s["blocks"] >= 1
                            for s in restores)


def test_a_span_is_cheap_with_the_profiler_off():
    from repro.serve.spans import Span

    class Stats:
        t = 0.0
    stats = Stats()
    n = 20000
    t = time.perf_counter()
    for i in range(n):
        with Span(stats, "t", "serve.test", rid=i, blk=1):
            pass
    per_span = (time.perf_counter() - t) / n
    assert 0 < stats.t < n * per_span
    # generous for a loaded CI machine: the loop opens about 15 a step
    assert per_span < 50e-6, per_span
