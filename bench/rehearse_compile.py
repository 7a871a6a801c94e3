#!/usr/bin/env python3
"""Compile a cell's decode step and its largest prefill for a TPU v5e that
is described, not attached, and print what the compiler's memory analysis
says of each. Compile-only: nothing runs, so nothing here is a time.

    JAX_PLATFORMS=cpu python3 bench/rehearse_compile.py <workload> [...]
"""
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(names) -> int:
    import dataclasses
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench.harness import ROOT as R, Cell, load_json
    from bench.traffic import prompt_lengths
    from repro.configs.base import ArchConfig
    from repro.models import build_model

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = load_json(R / "BENCHMARK.json")
    for name in names:
        cell = Cell.from_benchmark(bench, name)
        arch, serve = cell.config["arch"], cell.config["serve"]
        fields = {f.name for f in dataclasses.fields(ArchConfig)}
        model = build_model(ArchConfig(
            name=cell.config["name"],
            **{k: v for k, v in arch.items() if k in fields}))

        def place(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=chip), tree)

        params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        B = max(serve["batch_buckets"])
        cache = place(jax.eval_shape(
            lambda: model.init_cache(B, serve["max_len"])))
        block = serve["block_size"]
        s_max = -(-prompt_lengths(cell.traffic)[1] // block) * block

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                        sharding=chip)

        progs = {
            f"decode_step B={B} max_len={serve['max_len']}":
                jax.jit(model.decode_step).lower(
                    params, cache, sds((B, 1), "int32"), sds((B,), "int32"),
                    sds((B,), "bool")),
            f"prefill B={B} S={s_max}":
                jax.jit(model.prefill).lower(
                    params, sds((B, s_max), "int32"), sds((B,), "int32")),
        }
        for label, lowered in progs.items():
            m = lowered.compile().memory_analysis()
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(f"{name} {label}: argument_bytes "
                  f"{m.argument_size_in_bytes} output_bytes "
                  f"{m.output_size_in_bytes} temp_bytes "
                  f"{m.temp_size_in_bytes} alias_bytes "
                  f"{m.alias_size_in_bytes} total_bytes {total}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
