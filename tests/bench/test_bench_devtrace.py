"""The reduction from a profiler trace to device metrics, on small traces
recorded by ``jax.profiler``: four rounds of a ``decode_step`` program, a
``prefill`` program and an eager update, with a host span between them,
inside the ``bench.window`` annotation."""
import gzip
import pathlib

import pytest

from bench_tiny import ROOT  # noqa: F401
from bench import devtrace

DATA = pathlib.Path(__file__).parent / "data"
TRACES = sorted(p.name for p in DATA.glob("*.xplane.pb.gz"))


@pytest.fixture(params=TRACES)
def trace(request, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress((DATA / request.param).read_bytes()))
    return request.param, devtrace.reduce(str(path))


def test_window_and_busy_time(trace):
    _, t = trace
    assert 0 < t.window_s < 5
    busy = t.busy_s()
    assert 0 < busy < t.window_s
    for iv in t.busy.values():
        assert all(a < b for a, b in iv)
        assert all(iv[i][1] <= iv[i + 1][0] for i in range(len(iv) - 1))
        assert iv[0][0] >= t.window[0] and iv[-1][1] <= t.window[1]


def test_programs_by_name(trace):
    name, t = trace
    times = t.program_time()
    for prog in ("jit_decode_step", "jit_prefill"):
        assert prog in times and times[prog][0] > 0
    if name.startswith("tpu"):
        # one event per execution on the device's module line
        assert times["jit_decode_step"][1] == 4
        assert times["jit_prefill"][1] == 4
    # the device cannot run programs for longer than it was busy
    assert sum(s for s, _ in times.values()) <= t.busy_s() * len(t.busy) * 1.01


def test_idle_gaps_are_longest_first_and_named(trace):
    _, t = trace
    gaps = t.idle_gaps(10)
    assert 0 < len(gaps) <= 10
    lengths = [s for _, s in gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert sum(lengths) <= t.window_s - t.busy_s() + 1e-9
    # the host slept in "host.work" for 3 ms between programs
    assert any(label == "host.work" for label, _ in gaps)


def test_program_names_lose_their_ids():
    assert devtrace.program_name("jit_decode_step(12)") == "jit_decode_step"
    assert devtrace.program_name("jit_prefill") == "jit_prefill"
