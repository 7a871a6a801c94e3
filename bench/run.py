#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``. The last line of standard output is one JSON object;
the last lines of standard error are the numbers of the correctness check,
each beside its limit. With no accelerator, or fewer chips than the cell
asks for, it exits 1 and prints no result.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the compile cache lives in the checkout, at a fixed path: the program's
# own choice of directory follows this variable
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # small eager programs are cached too, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.harness import main
    sys.exit(main(t_start=T_START))
