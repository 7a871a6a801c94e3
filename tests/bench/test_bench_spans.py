"""A traced run of the tiny cell on the CPU reads the engine's spans and
counters: the per-layer metrics built on them, the idle gaps named after
them, and the engine loop's split from `bench/loop_split.py`.
"""
import numpy as np
import pytest

from bench_tiny import run_tiny
from bench import devtrace, loop_split


@pytest.fixture(scope="module")
def traced_split():
    """A traced run, and the engine loop's split read from its trace."""
    found = {}
    reduce = devtrace.reduce

    def reduce_and_split(path):
        trace = reduce(path)
        found.update(loop_split.split(trace, loop_split.spans(path), 2.0))
        return trace
    devtrace.reduce = reduce_and_split
    try:
        return run_tiny(trace=True, seed=3), found
    finally:
        devtrace.reduce = reduce


@pytest.fixture(scope="module")
def traced(traced_split):
    return traced_split[0]


@pytest.mark.parametrize("name", ["loop_lock_wait_share", "restore_host_ms",
                                  "swap_stall_ms", "d2h_copy_gbps",
                                  "disk_io_gbps"])
def test_traced_tiny_run_reads_the_engine_span_metrics(traced, name):
    """The readers of the engine's spans and counters find something to
    read in a cell that swaps through every tier."""
    assert traced["correct"], traced["checks"]
    value = traced["metrics"][name]["value"]
    assert np.isfinite(value) and value > 0


def test_idle_gaps_are_named_after_engine_spans(traced):
    labels = [label for label, _ in traced["breakdown"]["idle_gaps"]]
    assert any(label.startswith("serve.") for label in labels), labels


def test_loop_phases_cover_the_window_and_its_idle_time(traced_split):
    """The loop's phases cover the traced window, and the device's idle
    time lies under them, in the whole window and in each slice."""
    split = traced_split[1]
    assert split["phases_s"]["serve.decode"] > 0
    for part in [split] + split["slices"]:
        assert part["loop_covered_s"] >= 0.95 * part["seconds"], part
        assert part["idle_under_loop_s"] >= 0.9 * part["idle_s"], part
    assert sum(s["decode_steps"] for s in split["slices"]) \
        == split["decode_steps"] > 0
    assert split["streams"]["serve.d2h.copy"]["count"] > 0
