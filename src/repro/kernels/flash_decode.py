"""Pallas TPU kernels: flash-decoding and chunk-prefill attention over
the slot-addressed KV cache.

One-token decode attention for the serving tier: every generated token
streams the KV cache exactly once, in its stored precision.  Grid is
(slot, query-row tile, kv-block) with the KV sweep innermost, so the
online-softmax running state (max, sum, acc) of every kv head lives in
VMEM scratch across the blocks of one slot.

Three things distinguish this from the prefill flash kernel:

* **Grouped-query GQA in-kernel** — the q tile is the (Hkv, G, D)
  grouping of the query heads, and each KV head's (bk, D) slab is
  attended by its G query rows, so KV is never repeated (repeating a
  slot cache costs G× its HBM bytes).
* **Per-slot KV-length bounding** — ``kv_len (B,)`` is each slot's
  high-water mark (entries at index >= kv_len are guaranteed invalid,
  position −1).  Blocks entirely past it are skipped: their compute is
  predicated off AND their index map is clamped to the last live block,
  so the pipeline elides the HBM→VMEM copy.  Decode HBM traffic tracks
  actual occupancy, not capacity — and with pad-free chunked admission
  the fill is exactly the live tokens.
* **Fused Int8KV dequant** — int8 values and their per-(entry, head)
  f32 scales are read and dequantized inside the VMEM tile; decode never
  materializes a float copy of the cache.

Masking is identical to the jnp ref: stored position −1 is invalid,
``pos <= q_pos`` (causal), and ``pos > q_pos - window`` for sliding-
window layers.  A slot with no valid entries (kv_len == 0, or all
positions −1) produces zeros, matching ``ref.decode_attention_ref``.

``flash_chunk_prefill`` is the C-query sibling serving chunked pad-free
admission: the q tile carries the whole chunk's grouped query rows
(C × G) with per-row query positions (causality across the chunk is
pure position masking — the chunk's KV is already in the cache).
Decode is its C == 1 case: both run the one kernel below.

**Tiling.**  Mosaic requires the last two dims of every block to be
(8, 128)-divisible or whole.  A KV block therefore carries *all* kv
heads, ``(1, bk, Hkv, D)`` — whole trailing dims, one contiguous DMA
per block — and the kernel slices head ``h`` out of the VMEM tile.
Positions ride as ``(…, 1, bk)`` rows and Int8KV scales as
``(…, bk, Hkv)`` blocks, both whole in their trailing dims.

Both kernels speak the **paged pool** layout (docs/paged_kv.md): with a
``block_table`` (B, n_blocks) scalar-prefetch operand, k/v are an
(NB, BS, Hkv, D) pool of fixed-size blocks and the grid's KV-block index
resolves through the slot's table row inside the index maps — the DMA
stream touches exactly the slot's blocks.  The contiguous (B, S, Hkv, D)
layout is the same kernel over a pool of B·S/bk blocks addressed by an
iota table, so there is one addressing path.  A ``layer`` scalar-
prefetch operand reads one layer of a stacked (L, NB, BS, Hkv, D) pool
in place; a pool without it is the L = 1 case.  ``kv_block_size`` (the
tile helper shared with serve/kvcache.py) guarantees pool block ==
kernel block, and ``check_kv_block`` states which blocks the chip's
compiler accepts.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# The kernels' default KV tile (rows per grid step): a full 128-lane
# row of positions, and the block the serving engines round capacity to.
BLOCK_K = 128
# Query-row tile budget: the q/out tiles (double-buffered), the f32
# accumulator and the lane-padded running max/sum together hold about
# seven (Hkv, tq, D) f32-equivalents of VMEM; 2^19 elements keeps that
# inside the 16 MiB of VMEM a v5e kernel gets by default.
_Q_TILE_ELEMS = 1 << 19


def kv_block_size(capacity: int, block_k: int = BLOCK_K) -> int:
    """KV block granularity at a given per-slot capacity: the flash
    kernels' tile choice — min(block_k, capacity), halved until it
    divides capacity cleanly (floored at 8).  This is the single source
    of truth shared by the kernels, the serving engines' capacity
    rounding, and the paged ``BlockManager``'s physical block size (the
    paged pool's block == the kernel's KV grid block, so the block-table
    index map needs no sub-block arithmetic)."""
    bk = min(block_k, max(int(capacity), 1))
    while capacity % bk and bk > 8:
        bk //= 2
    return bk


def check_kv_block(block: int, capacity: int) -> None:
    """Raise unless ``block`` can be the paged pool's block at this
    per-slot ``capacity``.  Every operand block of the kernel is whole
    in its trailing two dims — KV ``(1, bk, Hkv, D)``, Int8KV scales
    ``(1, bk, Hkv)``, positions ``(1, 1, bk)`` — so Mosaic's (8, 128)
    rule holds for any block size and KV dtype (float, bf16 or int8;
    ``tests/test_tpu_compile.py`` compiles both ends of the range for a
    v5e).  What remains is the block table's: a slot's capacity must be
    a whole number of blocks."""
    if block < 1 or capacity % block:
        raise ValueError(f"KV block of {block} rows must be >= 1 and "
                         f"divide the slot capacity {capacity}")


def _kernel(kl_ref, tbl_ref, ly_ref, qp_ref, q_ref, k_ref, v_ref, pos_ref,
            *rest, scale: float, bk: int, n_k: int, hkv: int, window: int,
            int8: bool):
    del tbl_ref, ly_ref                  # consumed by the index maps only
    if int8:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kvl = kl_ref[bi]

    # Block liveness: the scheduler guarantees entries at index >= kv_len
    # are invalid, so blocks past the high-water mark contribute nothing.
    @pl.when(ki * bk < kvl)
    def _compute():
        pos = pos_ref[0]                                     # (1, bk)
        qp = qp_ref[0]                                       # (R, 1)
        idx = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        # pad query rows (qp == −1) have no valid key: pos >= 0 and
        # pos <= −1 can't both hold, so they finalize to exact zeros.
        valid = (pos >= 0) & (pos <= qp) & (idx < kvl)       # (R, bk)
        if window > 0:
            valid &= pos > qp - window
        keep = valid.astype(jnp.float32)
        if int8:
            # scale tiles arrive (Hkv, bk): one (bk, Hkv) transpose each
            ks, vs = ks_ref[0].T, vs_ref[0].T
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32) * scale      # (R, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)        # (bk, D)
            if int8:
                k = k * ks[:, h:h + 1]                       # (bk, 1) scales
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]                                # (R, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # explicit mask multiply: an all-invalid block has m_new ==
            # NEG_INF and exp(s - m_new) == 1 there — the mask zeroes it
            # so empty rows finalize to exactly 0 instead of a garbage
            # mean.
            p = jnp.exp(s - m_new) * keep
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
            m_ref[h] = m_new
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if int8:
                v = v * vs[:, h:h + 1]
            pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * alpha + pv

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pad_seq(x: Optional[jax.Array], pad: int, axis: int, value=0):
    if x is None or pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _as_blocks(x: Optional[jax.Array], bk: int) -> Optional[jax.Array]:
    """(B, S, ...) slot rows -> (B·S/bk, bk, ...) pool blocks (a reshape
    of major dims: no copy)."""
    if x is None:
        return None
    return x.reshape((-1, bk) + x.shape[2:])


def _q_tile(r: int, hkv: int, d: int) -> int:
    """Query rows per grid step: all R rows while the (Hkv, R, D) tiles
    fit the VMEM budget, else the largest multiple of 8 that divides R
    and fits.  Each extra row tile re-reads the slot's KV blocks."""
    lanes = -(-d // 128) * 128
    cap = _Q_TILE_ELEMS // (hkv * lanes)
    if r <= cap:
        return r
    for tq in range(cap - cap % 8, 7, -8):
        if r % tq == 0:
            return tq
    return r


def _attend(q, q_pos, k, v, cache_pos, kv_len, k_scale, v_scale,
            block_table, window, block_k, interpret, layer=None):
    """The one pallas_call behind both entry points.  q: (B, Hkv, R, D)
    grouped query rows; q_pos: (B, R) per-row positions.  With ``layer``
    the pool leaves are stacked (L, NB, BS, ...) and the layer is one
    more scalar-prefetch operand of the index maps."""
    b, hkv, r, d = q.shape
    if block_table is None:
        # contiguous slot rows == a pool of B·n_k blocks whose table is
        # an iota.  Prefer a block that divides S (halving down to 8)
        # over padding — padding copies the cache once per call.
        s = k.shape[1]
        bk = kv_block_size(s, block_k)
        pad = (-s) % bk
        k, v, k_scale, v_scale = (_as_blocks(_pad_seq(x, pad, 1), bk)
                                  for x in (k, v, k_scale, v_scale))
        cache_pos = _pad_seq(cache_pos, pad, 1, value=-1)
        n_k = (s + pad) // bk
        block_table = jnp.arange(b * n_k, dtype=jnp.int32).reshape(b, n_k)
    else:
        # pool block == kernel KV block by construction (kv_block_size)
        bk = k.shape[-3]
        n_k = block_table.shape[1]
    if layer is None:
        # one layer's pool is the stacked form with L = 1 (a reshape)
        k, v, k_scale, v_scale = (None if x is None else x[None]
                                  for x in (k, v, k_scale, v_scale))
        layer = 0
    int8 = k_scale is not None
    tq = _q_tile(r, hkv, d)

    def blk(bi, ki, kl, tbl):
        # Dead blocks re-map to the last live one: an unchanged block
        # index means the pipeline skips the HBM→VMEM copy entirely.
        last_live = jnp.maximum(pl.cdiv(kl[bi], bk) - 1, 0)
        return tbl[bi, jnp.minimum(ki, last_live)]

    def row_index(bi, qi, ki, kl, tbl, ly):
        return (bi, 0, qi, 0)

    def kv_index(bi, qi, ki, kl, tbl, ly):
        return (ly[0], blk(bi, ki, kl, tbl), 0, 0, 0)

    def scale_index(bi, qi, ki, kl, tbl, ly):
        return (ly[0], blk(bi, ki, kl, tbl), 0, 0)

    def pos_index(bi, qi, ki, kl, tbl, ly):
        # positions are one (NB, BS) pool shared by every layer
        return (blk(bi, ki, kl, tbl), 0, 0)

    # the layer axis is squeezed: the kernel sees (1, bk, Hkv, D) tiles
    in_specs = [
        pl.BlockSpec((1, tq, 1), lambda bi, qi, ki, *_: (bi, qi, 0)),
        pl.BlockSpec((1, hkv, tq, d), row_index),
        pl.BlockSpec((pl.squeezed, 1, bk, hkv, d), kv_index),
        pl.BlockSpec((pl.squeezed, 1, bk, hkv, d), kv_index),
        pl.BlockSpec((1, 1, bk), pos_index),
    ]
    operands = [q_pos.astype(jnp.int32)[:, :, None], q, k, v,
                cache_pos.reshape(-1, 1, bk)]
    if int8:
        # Scales ride as (…, Hkv, bk), bk on the lanes: XLA's default
        # TPU layout of an (…, BS, Hkv) f32 pool is that transpose, so
        # the swap is a bitcast where a (bk, Hkv) block would copy the
        # whole stacked pool into row-major order.
        in_specs += [pl.BlockSpec((pl.squeezed, 1, hkv, bk),
                                  scale_index)] * 2
        operands += [jnp.swapaxes(x, -1, -2) for x in (k_scale, v_scale)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, r // tq, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, tq, d), row_index),
        scratch_shapes=[
            pltpu.VMEM((hkv, tq, 1), jnp.float32),    # running max
            pltpu.VMEM((hkv, tq, 1), jnp.float32),    # running sum
            pltpu.VMEM((hkv, tq, d), jnp.float32),    # output accumulator
        ])
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, bk=bk, n_k=n_k, hkv=hkv, window=window,
        int8=int8)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, r, d), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), block_table.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_k", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 q_pos: jax.Array, cache_pos: jax.Array, kv_len: jax.Array,
                 *, k_scale: Optional[jax.Array] = None,
                 v_scale: Optional[jax.Array] = None,
                 block_table: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None,
                 window: int = 0, block_k: int = BLOCK_K,
                 interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, G, D) grouped queries.

    Contiguous (slot-rectangle) layout — ``block_table is None``:
    k/v: (B, S, Hkv, D) float — or int8 with ``k_scale``/``v_scale``
    (B, S, Hkv) f32 per-(entry, head) scales.  q_pos: (B,) absolute
    query positions; cache_pos: (B, S) stored positions (−1 invalid);
    kv_len: (B,) per-slot high-water mark (use S for "scan everything").

    Paged layout — ``block_table`` (B, n_blocks) int32: k/v are a global
    *pool* (NB, BS, Hkv, D) of fixed-size KV blocks (scales (NB, BS,
    Hkv); cache_pos (NB, BS)); slot ``b``'s logical KV block ``j`` lives
    in physical block ``block_table[b, j]``.  The grid's KV-block index
    resolves through the table inside the index maps, so the pipeline
    DMAs exactly the slot's blocks — there is no per-slot capacity
    rectangle in HBM at all.  Entries of the table beyond the slot's
    live region must still hold a *valid* physical block id (0 is fine):
    the kv_len clamp re-maps dead grid steps onto the last live block
    and predicates their compute off, exactly as in the contiguous
    layout.  ``kv_len`` remains the *logical* per-slot fill.

    ``layer`` (a scalar) reads one layer of a stacked pool: k/v (L, NB,
    BS, Hkv, D), scales (L, NB, BS, Hkv), cache_pos (NB, BS) as before.
    The index maps pick the layer, so the layer scan can carry the whole
    pool and no per-layer slice is ever materialized (docs/paged_kv.md).

    Returns (B, Hkv, G, D) in q.dtype.

    Callers should size S to a multiple of the KV block (the servers
    round capacity up) — ragged S first shrinks the block (halving down
    to 8) and only then pads, which costs a cache copy per call.  In the
    paged layout the kernel block IS the pool block (``kv_block_size``),
    so no shrink/pad path exists.
    """
    b, _, g, _ = q.shape
    q_rows = jnp.broadcast_to(q_pos.astype(jnp.int32)[:, None], (b, g))
    return _attend(q, q_rows, k, v, cache_pos, kv_len, k_scale, v_scale,
                   block_table, window, block_k, interpret, layer)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_k", "interpret"))
def flash_chunk_prefill(q: jax.Array, k: jax.Array, v: jax.Array,
                        q_pos: jax.Array, cache_pos: jax.Array,
                        kv_len: jax.Array,
                        *, k_scale: Optional[jax.Array] = None,
                        v_scale: Optional[jax.Array] = None,
                        block_table: Optional[jax.Array] = None,
                        layer: Optional[jax.Array] = None,
                        window: int = 0, block_k: int = BLOCK_K,
                        interpret: bool = False) -> jax.Array:
    """q: (B, Hkv, R, D) grouped chunk queries — R = C·G rows ordered
    (query, group), i.e. row ``c*G + g``; q_pos: (B, R) per-row absolute
    query positions, already G-repeated (−1 marks a pad query row, which
    returns exact zeros).  k/v: (B, S, Hkv, D) float — or int8 with
    ``k_scale``/``v_scale`` (B, S, Hkv) f32 scales.  cache_pos: (B, S)
    stored positions (−1 invalid); kv_len: (B,) per-slot post-write fill
    bounding the KV sweep (use S for "scan everything").  Returns
    (B, Hkv, R, D) in q.dtype.

    ``block_table`` (B, n_blocks) int32 switches to the paged-pool
    layout exactly as in ``flash_decode``: k/v (NB, BS, Hkv, D), scales
    (NB, BS, Hkv), cache_pos (NB, BS), and the KV-block grid index
    resolves through the slot's table row inside the index maps;
    ``layer`` reads one layer of a stacked pool as in ``flash_decode``.

    The chunk's own KV must already be resident in the cache (written at
    its rows, or concatenated for ring layouts): in-chunk causality is
    decided purely by ``pos <= q_pos``, identical to the decode kernel.
    """
    return _attend(q, q_pos, k, v, cache_pos, kv_len, k_scale, v_scale,
                   block_table, window, block_k, interpret, layer)
