"""Compile a cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <config> [<config> ...]

For each configuration: the one-jit weight build, and the paged chunk
and decode steps at the configuration's slots, capacity and pool, built
as ``PagedBatchServer`` builds them (Pallas kernels, the cache donated).
Prints each program's ``memory_analysis`` in GiB and whether it holds a
Pallas kernel, and exits non-zero if the compiler refuses one.  Nothing
runs and nothing is allocated: shapes come from ``jax.eval_shape``.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

GIB = 2 ** 30


def report(name, compiled) -> dict:
    mem = compiled.memory_analysis()
    row = {"program": name,
           "argument_gib": mem.argument_size_in_bytes / GIB,
           "output_gib": mem.output_size_in_bytes / GIB,
           "temp_gib": mem.temp_size_in_bytes / GIB,
           "alias_gib": mem.alias_size_in_bytes / GIB,
           "pallas": "tpu_custom_call" in compiled.as_text()}
    row["held_gib"] = (row["argument_gib"] + row["output_gib"]
                       + row["temp_gib"] - row["alias_gib"])
    print(json.dumps(row), flush=True)
    return row


def rehearse(config: dict, chip) -> list:
    from jax.sharding import SingleDeviceSharding
    from repro import flags
    from repro.core.quantize import policy_for
    from repro.kernels.flash_decode import kv_block_size
    from repro.serve.kvcache import abstract_paged_cache, paged_slot_axes
    from repro.serve.serve_step import (make_paged_chunk_prefill_step,
                                        make_paged_decode_step)
    from bench import harness as H
    from bench import weights
    flags.set_flags(kernel_path="pallas")
    s = SingleDeviceSharding(chip)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=s), tree)

    rows = []
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    build = jax.jit(weights.builder(config)).lower(key).compile()
    rows.append(report(f"{config['name']}:weights", build))
    cfg = H.arch_config(config)
    params = on_chip(jax.eval_shape(weights.builder(config),
                                    jax.random.key(0)))
    sv = config["server"]
    need = max(sv["max_prompt"] + sv["max_new_cap"],
               -(-sv["max_prompt"] // sv["prefill_chunk"])
               * sv["prefill_chunk"])
    cap = -(-need // 128) * 128
    block = kv_block_size(cap)
    n_table = cap // block
    slots, pool = sv["slots"], sv["pool_blocks"]
    prec = policy_for(config["precision"])
    cache = on_chip(abstract_paged_cache(cfg, slots, cap, pool, prec, block))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=s)
    table = jax.ShapeDtypeStruct((slots, n_table), jnp.int32, sharding=s)
    dec = jax.jit(make_paged_decode_step(cfg, policy=prec),
                  donate_argnums=(1,)).lower(params, cache, vec, vec, vec,
                                             table).compile()
    rows.append(report(f"{config['name']}:decode", dec))
    axes = paged_slot_axes(cfg, slots, cap, pool, prec, block)
    c = sv["prefill_chunk"]
    row = jax.ShapeDtypeStruct((1, c), jnp.int32, sharding=s)
    chunk = jax.jit(make_paged_chunk_prefill_step(cfg, axes=axes,
                                                  policy=prec),
                    donate_argnums=(1,)).lower(
        params, cache, row, row, jax.ShapeDtypeStruct((), jnp.int32,
                                                      sharding=s),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=s),
        jax.ShapeDtypeStruct((1, n_table), jnp.int32, sharding=s)).compile()
    rows.append(report(f"{config['name']}:chunk", chunk))
    return rows


def main(names) -> int:
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        config = json.loads((ROOT / "bench" / "configs"
                             / f"{name}.json").read_text())
        rehearse(config, topo.devices[0])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
