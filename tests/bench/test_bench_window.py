"""Window arithmetic of the end-to-end metrics, on hand-made timelines."""
import pytest

from bench_tiny import ROOT  # noqa: F401
from bench import harness
from bench.models import dense


def make_run(tokens, done, t0=10.0, t1=20.0):
    return harness.Run(arch={}, work=dense, peaks={}, t0=t0, t1=t1,
                       setup_s=3.5, sent={}, tokens=tokens,
                       done=done, decode=[], prefill=[], stats0={},
                       stats1={}, compiles=[9.0, 12.0, 21.0])


def read(name, run):
    return harness.module("metrics", name).read(run)


def steady(n_req=20, gap=0.1):
    """n_req requests, each emitting a token every ``gap`` seconds across
    the window and finishing after it."""
    tokens = {r: [10.0 + 0.003 * r + gap * i for i in range(101)]
              for r in range(n_req)}
    return tokens, {r: ts[-1] for r, ts in tokens.items()}


def test_output_tokens_over_window():
    tokens, done = steady()
    run = make_run(tokens, done)
    # 20 requests x 100 tokens in [10, 20), the last one at 20.0+ is out
    assert read("output_tok_s", run) == pytest.approx(
        sum(10.0 <= t < 20.0 for ts in tokens.values() for t in ts) / 10.0)


def test_a_stall_inside_the_window_raises_itl_p99():
    tokens, done = steady()
    base = read("itl_p99_ms", make_run(tokens, done))
    assert base == pytest.approx(100.0, rel=1e-6)
    # every request stalls for 3 s in the middle of the window
    stalled = {r: [t if t < 15.0 else t + 3.0 for t in ts]
               for r, ts in tokens.items()}
    run = make_run(stalled, {r: ts[-1] for r, ts in stalled.items()})
    assert read("itl_p99_ms", run) > 3000.0


def test_an_open_gap_at_window_end_counts():
    # one request emits twice early and then nothing until after the window
    tokens = {0: [10.5, 10.6, 25.0]}
    run = make_run(tokens, {0: 25.0})
    # gaps: 0.1 s closed, and 9.4 s open at the window's end
    assert read("itl_p99_ms", run) == pytest.approx(
        1e3 * (0.1 + 0.99 * (9.4 - 0.1)))
    # a request that finished inside the window leaves no open gap
    run = make_run({0: [10.5, 10.6]}, {0: 10.6})
    assert read("itl_p99_ms", run) == pytest.approx(100.0)


def test_setup_and_compiles_in_window():
    run = make_run({}, {})
    assert read("setup_s", run) == 3.5
    assert read("compiles_in_window", run) == 1.0
    assert read("itl_p99_ms", run) is None


def test_counter_metrics_read_deltas():
    tokens, done = steady(n_req=2)
    run = make_run(tokens, done)
    keys = ("offload_bytes", "reload_bytes", "disk_spill_bytes",
            "disk_load_bytes", "stall_time")
    run.stats0 = {k: 100.0 for k in keys}
    run.stats1 = {"offload_bytes": 1100.0, "reload_bytes": 2100.0,
                  "disk_spill_bytes": 100.0, "disk_load_bytes": 600.0,
                  "stall_time": 102.5}
    n = run.output_tokens()
    assert read("kv_moved_bytes_per_tok", run) == pytest.approx(3500.0 / n)
    assert read("stall_share", run) == pytest.approx(25.0)


def test_steps_group_tokens_by_engine_and_step():
    run = make_run({}, {})
    run.decode = [(11.0, "a", 7, 100), (11.0, "a", 7, 50), (11.1, "a", 8, 101),
                  (11.0, "b", 7, 9), (25.0, "a", 9, 102)]
    steps = sorted((len(l), sum(l)) for _, l in run.steps("decode"))
    assert steps == [(1, 9), (1, 101), (2, 150)]
