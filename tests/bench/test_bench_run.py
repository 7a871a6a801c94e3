"""The benchmark's entry and its data: the run refuses to measure without
a chip, or without the program, and every name in BENCHMARK.json finds its
file."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT
from bench import harness

BENCH = harness.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _entry(cwd, workload="olmo-1b.swap-long"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_an_accelerator_exits_nonzero_and_prints_no_result():
    proc = _entry(ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "accelerator" in proc.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    paths, the entry fails, and a run past the chip check fails too."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _entry(tmp_path)
    assert proc.returncode != 0 and "{" not in proc.stdout
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "-c", "import sys; sys.path.insert(0, "
         "'tests/bench'); import bench_tiny; bench_tiny.run_tiny()"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and "{" not in proc.stdout
    assert "No module named 'repro'" in proc.stderr


def test_names_units_and_keys():
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(BENCH) == keys
    names = [e["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in BENCH[part]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in BENCH["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = harness.Cell.from_benchmark(BENCH, cell)
    assert c.chips in (1, 4)
    assert c.config["reduced"] == [] or all(NAME.match(k)
                                            for k in c.config["reduced"])
    assert (ROOT / "bench/models" / f"{c.config['model']}.py").is_file()
    assert (ROOT / "bench/loops" / f"{c.traffic['loop']}.py").is_file()
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.module("metrics", m["name"]).read)
    assert {"token_gap_max", "checked_tokens"} <= set(c.limits)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer


def test_shares_of_a_roofline_or_peak_are_percent():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
