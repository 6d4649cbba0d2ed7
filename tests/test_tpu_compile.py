"""Compile the serving kernels for a TPU v5e that is described, not attached.

Interpret-mode parity (test_flash_decode, test_chunked_prefill,
test_paged_kv, test_kernels) checks the kernels' arithmetic but not what
the chip's compiler accepts: block shapes whose last two dims are
neither (8, 128)-divisible nor whole, and more VMEM than a kernel may
use, are refused only by Mosaic.  These tests hand the installed TPU
compiler the kernels at ``internlm2-1.8b`` widths (head_dim 128, 8 kv
heads, G = 2, 128-row pool blocks, 256-token chunks), ``mamba_scan`` at
``zamba2-2.7b`` widths, and the full-width paged decode and chunk steps,
and check that each compiles into a Pallas kernel (``tpu_custom_call``)
that fits one chip.  At the chip benchmark's pool the paged steps must
also update the donated pool in place: no whole-pool copy and no
per-layer pool slice in the compiled program.

The topology is described only inside the module fixture, so importing
this file touches no TPU library, and the compilation cache is off
around these compiles (an entry written for a described chip cannot be
read back without one).
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, flags
from repro.core.quantize import policy_for, serving_params
from repro.kernels import flash_decode as fd
from repro.kernels import int8_matmul as im
from repro.kernels import mamba_scan as ms
from repro.models.params import abstract_params
from repro.serve.kvcache import abstract_paged_cache, paged_slot_axes
from repro.serve.serve_step import (make_paged_chunk_prefill_step,
                                    make_paged_decode_step)

# internlm2-1.8b attention widths, and the smoke run's serving shape
HKV, G, D = 8, 2, 128
SLOTS, CAPACITY, BLOCK, CHUNK = 8, 1024, 128, 256
V5E_HBM = 16 * 2**30
# the chip benchmark's internlm2-chat serving shape: 192 pool blocks
CELL_SLOTS, CELL_CAPACITY, CELL_BLOCKS = 16, 1536, 192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        saved = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", saved)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool_args(s, kv_dtype, block=BLOCK, n_blocks=SLOTS * CAPACITY // BLOCK):
    kv = _spec(s, (n_blocks, block, HKV, D), kv_dtype)
    scales = ()
    if kv_dtype == jnp.int8:
        scales = (_spec(s, (n_blocks, block, HKV), jnp.float32),) * 2
    return kv, _spec(s, (n_blocks, block), jnp.int32), scales


def _paged_decode(q, k, v, qp, pos, kl, tbl, *scales):
    kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
    return fd.flash_decode(q, k, v, qp, pos, kl, block_table=tbl, **kw)


def _paged_chunk(q, k, v, qp, pos, kl, tbl, *scales):
    kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
    return fd.flash_chunk_prefill(q, k, v, qp, pos, kl, block_table=tbl,
                                  **kw)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_flash_decode_paged_compiles(one_chip, kv_dtype):
    s = one_chip
    kv, pos, scales = _pool_args(s, kv_dtype)
    vec = _spec(s, (SLOTS,), jnp.int32)
    _compile(_paged_decode, _spec(s, (SLOTS, HKV, G, D), jnp.bfloat16),
             kv, kv, vec, pos, vec,
             _spec(s, (SLOTS, CAPACITY // BLOCK), jnp.int32), *scales)


# G = 2 is internlm2-1.8b; G = 4 is granite-3-8b (8 kv heads of 128),
# whose 1024 query rows per 256-token chunk take two VMEM row tiles.
@pytest.mark.parametrize("kv_dtype,g", [
    (jnp.bfloat16, G), (jnp.int8, G), (jnp.bfloat16, 4), (jnp.int8, 4)],
    ids=["bf16", "int8", "bf16-row-tiled", "int8-row-tiled"])
def test_flash_chunk_prefill_paged_compiles(one_chip, kv_dtype, g):
    s = one_chip
    kv, pos, scales = _pool_args(s, kv_dtype)
    rows = CHUNK * g
    assert rows // fd._q_tile(rows, HKV, D) == g // G
    _compile(_paged_chunk, _spec(s, (1, HKV, rows, D), jnp.bfloat16),
             kv, kv, _spec(s, (1, rows), jnp.int32), pos,
             _spec(s, (1,), jnp.int32),
             _spec(s, (1, CAPACITY // BLOCK), jnp.int32), *scales)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_smallest_pool_block_compiles(one_chip, kv_dtype):
    """``check_kv_block`` accepts any block that divides capacity: every
    operand block is whole in its trailing dims, so an 8-row pool block
    compiles as well as the 128-row default."""
    s = one_chip
    kv, pos, scales = _pool_args(s, kv_dtype, block=8, n_blocks=64)
    vec = _spec(s, (SLOTS,), jnp.int32)
    _compile(_paged_decode, _spec(s, (SLOTS, HKV, G, D), jnp.bfloat16),
             kv, kv, vec, pos, vec, _spec(s, (SLOTS, 8), jnp.int32), *scales)


def test_flash_decode_contiguous_compiles(one_chip):
    s = one_chip
    kv = _spec(s, (SLOTS, CAPACITY, HKV, D), jnp.bfloat16)
    vec = _spec(s, (SLOTS,), jnp.int32)
    _compile(fd.flash_decode, _spec(s, (SLOTS, HKV, G, D), jnp.bfloat16),
             kv, kv, vec, _spec(s, (SLOTS, CAPACITY), jnp.int32), vec)


@pytest.mark.parametrize("m", [SLOTS, 512])       # decode rows, prefill rows
def test_int8_matmul_compiles(one_chip, m):
    s = one_chip
    k, n = 2048, 8192                              # internlm2 d_model, d_ff
    _compile(im.int8_matmul, _spec(s, (m, k), jnp.int8),
             _spec(s, (k, n), jnp.int8), _spec(s, (m,), jnp.float32),
             _spec(s, (n,), jnp.float32))


def test_mamba_scan_compiles(one_chip):
    s = one_chip
    cfg = configs.get("zamba2-2.7b")
    di, n, seq = cfg.d_inner, cfg.ssm_state, 256
    x = _spec(s, (1, seq, di), jnp.float32)
    bc = _spec(s, (1, seq, n), jnp.float32)
    _compile(ms.mamba_scan, x, x, bc, bc, _spec(s, (di, n), jnp.float32))


def _served_params(cfg, prec):
    """The abstract params tree a server holds (``serving_params``)."""
    return jax.eval_shape(
        lambda p: serving_params(p, prec, cfg.activation_dtype),
        abstract_params(cfg))


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_paged_steps_fit_one_chip(one_chip, precision, monkeypatch):
    """The full-width internlm2-1.8b paged decode and chunk steps, as the
    server builds them, compile through the Pallas kernels and fit one
    v5e's HBM (shapes from ``jax.eval_shape``; nothing is allocated)."""
    monkeypatch.setitem(flags.FLAGS, "kernel_path", "pallas")
    s = one_chip
    cfg = configs.get("internlm2-1.8b")
    prec = policy_for(precision)
    n_blocks = SLOTS * CAPACITY // BLOCK

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(s, a.shape, a.dtype), tree)

    params = on_chip(_served_params(cfg, prec))
    cache = on_chip(abstract_paged_cache(cfg, SLOTS, CAPACITY, n_blocks,
                                         prec, BLOCK))
    vec = _spec(s, (SLOTS,), jnp.int32)
    decode = _compile(make_paged_decode_step(cfg, policy=prec), params,
                      cache, vec, vec, vec,
                      _spec(s, (SLOTS, CAPACITY // BLOCK), jnp.int32))
    axes = paged_slot_axes(cfg, SLOTS, CAPACITY, n_blocks, prec, BLOCK)
    row = _spec(s, (1, CHUNK), jnp.int32)
    chunk = _compile(make_paged_chunk_prefill_step(cfg, axes=axes,
                                                   policy=prec),
                     params, cache, row, row, _spec(s, (), jnp.int32),
                     _spec(s, (1,), jnp.int32),
                     _spec(s, (1, CAPACITY // BLOCK), jnp.int32))
    for compiled in (decode, chunk):
        mem = compiled.memory_analysis()
        held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert held < V5E_HBM, held


def _results(hlo_text):
    """(name, opcode, dtype, shape) of every instruction in an HLO
    module, those inside fused computations included."""
    pat = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = (\w+)\[([\d,]*)\]"
                     r"(?:\{[^}]*\})? ([\w-]+)\(", re.M)
    for name, dtype, dims, opcode in pat.findall(hlo_text):
        yield name, opcode, dtype, tuple(int(x) for x in dims.split(",") if x)


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_paged_steps_update_pool_in_place(one_chip, precision, monkeypatch):
    """The full-width paged decode and chunk steps at the benchmark
    cell's pool, jitted with the cache donated as ``PagedBatchServer``
    builds them, update the pool in place: the layer scan carries the
    stacked pool and the kernels index its layer, so no whole-pool copy
    or write-back and no per-layer pool slice appears.  The weights are
    held as the server holds them (``serving_params``), so no step
    converts a weight to bf16: no ``convert`` to bf16 yields a weight's
    (K, N) matrix, a layer's slice or the whole stack; and the
    temporaries hold less than one pool leaf."""
    monkeypatch.setitem(flags.FLAGS, "kernel_path", "pallas")
    s = one_chip
    cfg = configs.get("internlm2-1.8b")
    prec = policy_for(precision)
    n_table = CELL_CAPACITY // BLOCK

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(s, a.shape, a.dtype), tree)

    params = on_chip(_served_params(cfg, prec))
    cache = on_chip(abstract_paged_cache(cfg, CELL_SLOTS, CELL_CAPACITY,
                                         CELL_BLOCKS, prec, BLOCK))
    vec = _spec(s, (CELL_SLOTS,), jnp.int32)
    decode = jax.jit(make_paged_decode_step(cfg, policy=prec),
                     donate_argnums=(1,)).lower(
        params, cache, vec, vec, vec,
        _spec(s, (CELL_SLOTS, n_table), jnp.int32)).compile()
    axes = paged_slot_axes(cfg, CELL_SLOTS, CELL_CAPACITY, CELL_BLOCKS,
                           prec, BLOCK)
    row = _spec(s, (1, CHUNK), jnp.int32)
    chunk = jax.jit(make_paged_chunk_prefill_step(cfg, axes=axes,
                                                  policy=prec),
                    donate_argnums=(1,)).lower(
        params, cache, row, row, _spec(s, (), jnp.int32),
        _spec(s, (1,), jnp.int32),
        _spec(s, (1, n_table), jnp.int32)).compile()

    pools = jax.tree.leaves({k: cache[k] for k in ("k", "v")})
    whole = {p.shape for p in pools}
    per_layer = {p.shape[1:] for p in pools}
    matrices = {w.shape[-2:] for w in jax.tree.leaves(abstract_params(cfg))
                if w.ndim >= 2}
    one_pool = max(math.prod(p.shape) * np.dtype(p.dtype).itemsize
                   for p in pools)
    for compiled in (decode, chunk):
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        for name, opcode, dtype, shape in _results(text):
            assert not (opcode == "convert" and dtype == "bf16"
                        and shape[-2:] in matrices), (name, shape)
            if shape in whole:
                # copy-start/-done pairs that move the small f32 Int8KV
                # scale pools to on-chip memory and back are the
                # compiler's prefetch, not a copy in HBM
                assert opcode != "copy", name
                assert not (opcode == "fusion" and "copy" in name), name
                assert opcode != "dynamic-update-slice", name
                assert "dynamic-update-slice" not in name, name
            squeezed = tuple(shape[1:]) if shape[:1] == (1,) else shape
            assert squeezed not in per_layer, (name, shape)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < one_pool, (temp, one_pool)
