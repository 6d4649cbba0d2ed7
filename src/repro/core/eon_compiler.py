"""EON Compiler analogue (paper C4): interpreter-less AOT deployment.

Edge Impulse's EON Compiler generates C++ that calls kernels directly,
deleting the TFLM graph interpreter.  The JAX analogue of that
interpreter is the trace + op-by-op dispatch layer: the deployment
artifact here is a **serialized XLA executable** (``jax.export``) that
runs with zero Python tracing / dispatch per call, plus its static
resource report — the exact RAM/flash story of Table 4 transposed to
(HBM, executable bytes).

``benchmarks/table4_memory.py`` measures both modes on CPU: eager
(op-by-op dispatch ≙ interpreter) vs AOT executable (≙ EON).
"""
from __future__ import annotations

import dataclasses
import pickle
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jax_export


@dataclasses.dataclass
class CompiledArtifact:
    name: str
    serialized: bytes                  # portable executable blob
    input_specs: Any
    memory: Dict[str, int]
    flops: float
    compile_time_s: float

    @property
    def artifact_bytes(self) -> int:
        return len(self.serialized)

    def save(self, path: Path) -> None:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(pickle.dumps(self))

    @staticmethod
    def load(path: Path) -> "CompiledArtifact":
        return pickle.loads(Path(path).read_bytes())

    def rehydrate(self) -> Callable:
        """Deserialize into a callable that never re-traces."""
        exported = jax_export.deserialize(self.serialized)
        return jax.jit(exported.call)


def compile_fn(fn: Callable, *abstract_args, name: str = "fn",
               static_fn_args: Optional[Dict] = None) -> CompiledArtifact:
    """AOT lower + compile + serialize ``fn(*args)``."""
    t0 = time.time()
    jfn = jax.jit(fn)
    exported = jax_export.export(jfn)(*abstract_args)
    blob = exported.serialize()
    lowered = jfn.lower(*abstract_args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    dt = time.time() - t0
    return CompiledArtifact(
        name=name, serialized=blob, input_specs=abstract_args,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        flops=float(cost.get("flops", 0.0)),
        compile_time_s=dt)


def compile_impulse(impulse, batch_size: int = 1,
                    int8: bool = False) -> CompiledArtifact:
    """Deploy an Impulse: one executable covering DSP + NN end-to-end."""
    if isinstance(impulse.input_shape, int):
        raw_shape = (batch_size, impulse.input_shape)
    else:
        raw_shape = (batch_size,) + tuple(impulse.input_shape)
    raw = jax.ShapeDtypeStruct(raw_shape, jnp.float32)

    if int8:
        assert impulse.qparams is not None
        from repro.core.quantize import fake_quant_params
        frozen = fake_quant_params(impulse.qparams)
    else:
        frozen = impulse.params

    def deploy(x):
        return impulse.learn.apply(frozen, impulse.dsp.apply(x))

    return compile_fn(deploy, raw,
                      name=f"{impulse.dsp.name}+{impulse.learn.name}"
                           f"{'+int8' if int8 else ''}")


def compile_serve_decode(cfg, params, *, slots: int, capacity: int,
                         pool_blocks: int, rules=None, mesh=None,
                         policy=None, block_size: Optional[int] = None
                         ) -> CompiledArtifact:
    """Serve-from-artifact hook (paper C4, end-to-end): AOT-compile the
    paged decode step into a ``CompiledArtifact`` so the server's hot
    loop runs the same kind of serialized executable we "deploy" — zero
    Python tracing per token.

    ``slots`` is the engine's decode batch (slot count), ``capacity`` the
    per-slot logical KV length (max prompt + max generation budget), and
    ``pool_blocks`` the physical blocks of the paged pool
    (``kvcache.abstract_paged_cache``) of ``block_size`` rows (default
    ``kv_block_size(capacity)``).  ``policy`` (``PrecisionPolicy``)
    lowers the int8 variant: QTensor params and an Int8KV cache.

    The decode signature is ``(params, cache, token, position, kv_len,
    block_table)``: with pad-free chunked admission a slot's logical KV
    index equals its entry's absolute position, so there is no separate
    write index; ``kv_len`` (slots,) is the scheduler's exact per-slot
    fill (``position + 1``; 0 = idle or mid-prefill slot, whose row the
    step neither reads nor writes) and ``block_table`` (slots,
    capacity // BS) int32 maps each slot's logical blocks to pool blocks.

    The artifact's static resource report carries the KV-cache HBM
    footprint of both precisions, so the deploy decision can read the
    delta without compiling twice — Table 4's RAM/flash story transposed
    to the serving tier — and prices the pool per block
    (``kv_block_bytes``/``kv_pool_blocks``) so live-KV HBM can be read at
    any target occupancy, not just the worst case.
    """
    from repro.serve.kvcache import (abstract_paged_cache,
                                     decode_cache_nbytes, kv_block_size,
                                     kv_pool_block_bytes)
    from repro.serve.serve_step import make_paged_decode_step

    step = make_paged_decode_step(cfg, rules=rules, mesh=mesh,
                                  policy=policy)
    params_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype),
        params)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32)
    suffix = ""
    if policy is not None and policy.weights == "int8":
        suffix = "-int8"
    bs = block_size or kv_block_size(capacity)
    cache_abs = abstract_paged_cache(cfg, slots, capacity, pool_blocks,
                                     policy, bs)
    table = jax.ShapeDtypeStruct((slots, capacity // bs), jnp.int32)
    art = compile_fn(
        step, params_abs, cache_abs, vec, vec, vec, table,
        name=f"{cfg.name}-decode-b{slots}-s{capacity}"
             f"-paged{pool_blocks}x{bs}{suffix}")
    art.memory["kv_block_bytes"] = kv_pool_block_bytes(cfg, capacity,
                                                       policy, bs)
    art.memory["kv_pool_blocks"] = pool_blocks
    art.memory["kv_cache_bytes"] = decode_cache_nbytes(cache_abs)
    art.memory["kv_cache_bytes_float"] = (
        art.memory["kv_cache_bytes"] if suffix == ""
        else decode_cache_nbytes(
            abstract_paged_cache(cfg, slots, capacity, pool_blocks, None,
                                 bs)))
    art.memory["param_bytes"] = decode_cache_nbytes(params_abs)
    return art


def measure_dispatch_overhead(fn: Callable, *args, iters: int = 20
                              ) -> Dict[str, float]:
    """Interpreter-vs-EON microbenchmark: eager dispatch vs AOT call."""
    # eager (op-by-op "interpreter" path)
    with jax.disable_jit():
        fn(*args)  # warm
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(*args))
        eager = (time.perf_counter() - t0) / iters

    jfn = jax.jit(fn)
    jax.block_until_ready(jfn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(jfn(*args))
    aot = (time.perf_counter() - t0) / iters
    return {"eager_us": eager * 1e6, "aot_us": aot * 1e6,
            "speedup": eager / max(aot, 1e-12)}
