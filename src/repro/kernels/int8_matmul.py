"""Pallas TPU kernel: blocked int8×int8 matmul, dequant fused in epilogue.

The EON-quantization serving path (paper C5): weights and activations are
int8, the MXU runs the int8 systolic path (2× bf16 throughput on v5e),
and the per-channel dequant scales are applied once in the output
epilogue instead of materializing a dequantized weight matrix in HBM.

Blocking: (bm × bk) · (bk × bn) tiles staged in VMEM, K innermost so the
int32 accumulator lives in a VMEM scratch across the K sweep.  Tile dims
default to 128/256 — multiples of the 128-wide MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k_idx == n_k - 1)
    def _epilogue():
        scale = xs_ref[...] * ws_ref[...]                   # (bm, 1)·(1, bn)
        o_ref[...] = acc_ref[...].astype(jnp.float32) * scale


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_matmul(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array,
                w_scale: jax.Array, *, bm: int = 128, bn: int = 128,
                bk: int = 256, interpret: bool = False) -> jax.Array:
    """x_q: (M, K) int8; w_q: (K, N) int8; x_scale: (M,) f32 per-row;
    w_scale: (N,) f32 per-channel.  Returns (M, N) f32.

    Ragged M/K/N (not multiples of the block dims) are zero-padded up to
    the tile grid and the output sliced back — exact, because zero int8
    entries contribute nothing to the int32 dot and padded output
    rows/cols are dropped.  Tiles stay (8, 128)-aligned rather than
    shrinking to the ragged remainder (misaligned tiles stall the MXU).
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (k, k2)
    # Clamp oversized blocks to the (aligned) problem dim, then pad every
    # dim up to its block multiple.
    bm = min(bm, _round_up(m, 8))
    bn = min(bn, _round_up(n, 128))
    bk = min(bk, _round_up(k, 128))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    if (mp, np_, kp) != (m, n, k):
        x_q = jnp.pad(x_q, ((0, mp - m), (0, kp - k)))
        w_q = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
        x_scale = jnp.pad(x_scale, (0, mp - m))
        w_scale = jnp.pad(w_scale, (0, np_ - n))
    n_k = kp // bk
    # scales ride as a (Mp, 1) column and a (1, Np) row: 2-D blocks whose
    # trailing dims Mosaic tiles like XLA does (a 1-D block does not)
    x_scale = x_scale.reshape(mp, 1)
    w_scale = w_scale.reshape(1, np_)

    grid = (mp // bm, np_ // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, x_scale, w_scale)
    return out[:m, :n] if (mp, np_) != (m, n) else out
