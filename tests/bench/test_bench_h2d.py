"""The h2d stream's copy rate, ``h2d_copy_gbps``: read from the engine's
counters, absent where the program has no ``serve.h2d.copy`` span, and
found in a traced run of the tiny cell, whose resumes stage their blocks
on the device."""
import numpy as np
import pytest

from bench_tiny import run_tiny
from bench import devtrace, harness, loop_split


def read(stats0, stats1):
    run = harness.Run(arch={}, work=None, peaks={}, t0=0.0, t1=1.0,
                      setup_s=0.0, sent={}, tokens={}, done={}, decode=[],
                      prefill=[], stats0=stats0, stats1=stats1, compiles=[])
    return harness.module("metrics", "h2d_copy_gbps").read(run)


def test_h2d_copy_gbps_is_bytes_over_span_seconds():
    stats0 = {"h2d_copy_bytes": 1e9, "h2d_copy_time": 2.0}
    stats1 = {"h2d_copy_bytes": 4e9, "h2d_copy_time": 4.0}
    assert read(stats0, stats1) == pytest.approx(1.5)


@pytest.mark.parametrize("stats", [{}, {"h2d_copy_bytes": 0,
                                        "h2d_copy_time": 0.0}])
def test_h2d_copy_gbps_is_absent_without_copies(stats):
    """A program without the span, or a window with no staged block,
    reports nothing."""
    assert read(dict(stats), dict(stats)) is None


@pytest.fixture(scope="module")
def traced_split():
    found = {}
    reduce = devtrace.reduce

    def reduce_and_split(path):
        trace = reduce(path)
        found.update(loop_split.split(trace, loop_split.spans(path), 2.0))
        return trace
    devtrace.reduce = reduce_and_split
    try:
        return run_tiny(trace=True, seed=2**31 + 11), found
    finally:
        devtrace.reduce = reduce


def test_traced_tiny_run_reads_h2d_copy_gbps(traced_split):
    result, split = traced_split
    assert result["correct"], result["checks"]
    value = result["metrics"]["h2d_copy_gbps"]["value"]
    assert np.isfinite(value) and value > 0
    assert split["streams"]["serve.h2d.copy"]["count"] > 0
