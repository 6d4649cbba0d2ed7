"""Foundational layers: norms, rotary embeddings, attention variants, MLP.

All functions are pure: ``f(params, x, ...) -> y``.  Activation sharding is
expressed through logical-axis constraints (``sharding.policy.constrain``)
so the same model code runs unsharded on CPU and fully sharded on a pod.

Attention comes in three structurally different lowerings (chosen
statically per layer/shape so the HLO is honest about FLOPs and memory):

* ``full_attention``     — plain O(S^2) scores; short sequences.
* ``chunked_attention``  — ``lax.scan`` over KV chunks with online softmax
                           (flash-attention schedule in jnp); long sequences.
* ``local_attention``    — sliding-window via the two-chunk band trick;
                           O(S * 2W) FLOPs, no scan carry.
* decode attention       — one query step against the slot-addressed KV
                           cache, dispatched through
                           ``kernels.ops.decode_attention``: the Pallas
                           flash-decode kernel on TPU (per-slot kv_len
                           bounding, in-tile Int8KV dequant), the jnp
                           grouped-q einsum ref elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import flags
from repro.core.quantize import (Int8KV, PrecisionPolicy, dequant_kv,
                                 quant_kv)
from repro.kernels.ops import chunk_attention, decode_attention, quant_matmul
from repro.sharding.policy import constrain

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# KV-cache representation helpers (PrecisionPolicy, serving tier)
# ---------------------------------------------------------------------------
def _constrain_decode_kv(cache):
    """A stacked pool's leading layer axis stays unconstrained."""
    def lead(x, n):
        return (None,) * (x.ndim - n)
    if isinstance(cache, Int8KV):
        return Int8KV(
            constrain(cache.q, lead(cache.q, 4) + (
                "act_batch", "act_cache_seq", "act_kv_heads", None)),
            constrain(cache.scale, lead(cache.scale, 3) + (
                "act_batch", "act_cache_seq", "act_kv_heads")))
    return constrain(cache, lead(cache, 4) + (
        "act_batch", "act_cache_seq", "act_kv_heads", None))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rms_norm(scale: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,), float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Standard RoPE. x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_freqs(x.shape[-1], theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, D/2)
    angles = angles[..., None, :]                                # (..., S, 1, D/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): head_dim/2 frequencies split into
    (temporal, height, width) sections, each rotated by its own position
    stream.  positions: (..., S, 3)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                  # (D/2,)
    assert sum(sections) == d // 2, (sections, d)
    # Build per-frequency position selection: section i uses positions[..., i].
    sec_ids = jnp.repeat(jnp.arange(3), jnp.array(sections),
                         total_repeat_length=d // 2)              # (D/2,)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(sec_ids, positions.shape[:-1] + (d // 2,)).astype(jnp.int32),
        axis=-1)                                                  # (..., S, D/2)
    angles = (pos * freqs)[..., None, :]                          # (..., S, 1, D/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def position_encode(q: jax.Array, k: jax.Array, positions: jax.Array,
                    variant: str, theta: float,
                    sections: Tuple[int, int, int]) -> Tuple[jax.Array, jax.Array]:
    if variant == "mrope":
        return (apply_mrope(q, positions, theta, sections),
                apply_mrope(k, positions, theta, sections))
    if variant == "rope":
        return (apply_rope(q, positions, theta),
                apply_rope(k, positions, theta))
    if variant == "none":
        return q, k
    raise ValueError(f"unknown rope variant {variant!r}")


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------
def _repeat_kv(kv: jax.Array, hq: int, axis: int = 2) -> jax.Array:
    """Broadcast KV heads up to Hq.  A reshape of the *query* head dim
    into (Hkv, group) would split a model-axis-sharded dimension into
    factors GSPMD can only partially shard (measured: full-replication
    bailouts → 16x attention flops); repeating the (replicated or
    cleanly-sharded) KV heads keeps the einsum dims 1:1 with shardings.
    """
    hkv = kv.shape[axis]
    if hkv == hq:
        return kv
    return jnp.repeat(kv, hq // hkv, axis=axis)


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Sq, Hq, D); k: (B, Sk, Hkv, D) -> (B, Hq, Sq, Sk)."""
    k = _repeat_kv(k, q.shape[2])
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_combine(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: (B, Hq, Sq, Sk); v: (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    v = _repeat_kv(v, p.shape[1])
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_positions: jax.Array, k_positions: jax.Array,
                   window: int = 0, causal: bool = True) -> jax.Array:
    """Plain attention with optional causal / sliding-window masking.

    positions are (B, S) absolute indices (mask is position-based so the
    same code serves packed/shifted sequences and cache decoding).
    Negative key positions mark invalid entries and are never attended.
    """
    scale = q.shape[-1] ** -0.5
    scores = _gqa_scores(q * scale, k)                       # (B,Hq,Sq,Sk) f32
    qp = q_positions[:, None, :, None]
    kp = k_positions[:, None, None, :]
    mask = kp >= 0
    if causal:
        mask = jnp.logical_and(mask, kp <= qp)
    if window > 0:
        mask = jnp.logical_and(mask, kp > qp - window)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return _gqa_combine(p.astype(v.dtype), v)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_positions: jax.Array, k_positions: jax.Array,
                      chunk: int = 1024, causal: bool = True) -> jax.Array:
    """Online-softmax attention: ``lax.scan`` over KV chunks.

    The flash-attention schedule expressed in jnp: memory is
    O(Sq * chunk) instead of O(Sq * Sk); this is the ref/HLO twin of
    ``kernels/flash_attention.py``.
    """
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    assert sk % chunk == 0, (sk, chunk)
    n_chunks = sk // chunk
    scale = d ** -0.5
    qs = (q * scale).astype(jnp.float32)

    k_c = k.reshape(b, n_chunks, chunk, *k.shape[2:])
    v_c = v.reshape(b, n_chunks, chunk, *v.shape[2:])
    kp_c = k_positions.reshape(b, n_chunks, chunk)
    # scan carries: (acc (B,Sq,Hq,D) f32, row max m, row sum l) per query.
    acc0 = jnp.zeros((b, sq, hq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)

    def body(carry, inputs):
        acc, m, l = carry
        kc, vc, kpc = inputs                                   # chunk leaves
        s = _gqa_scores(qs, kc)                                # (B,Hq,Sq,C)
        qp = q_positions[:, None, :, None]
        kp = kpc[:, None, None, :]
        mask = kp >= 0
        if causal:
            mask = jnp.logical_and(mask, kp <= qp)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        if flags.get("bf16_attn_p"):
            # flash-style: p consumed in bf16 by the MXU, f32 accumulate
            pv = _gqa_combine(p.astype(v.dtype), vc).astype(jnp.float32)
        else:
            pv = _gqa_combine(p, vc.astype(jnp.float32))       # (B,Sq,Hq,D)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return (acc_new, m_new, l_new), None

    (acc, m, l), _ = lax.scan(
        body, (acc0, m0, l0),
        (jnp.moveaxis(k_c, 1, 0), jnp.moveaxis(v_c, 1, 0),
         jnp.moveaxis(kp_c, 1, 0)))
    out = acc / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
    return out.astype(v.dtype)


def local_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_positions: jax.Array, k_positions: jax.Array,
                    window: int) -> jax.Array:
    """Sliding-window attention via the two-chunk band trick.

    With chunk length C == window, query chunk i can only see key chunks
    i-1 and i, so the banded score tensor is (B, H, nC, C, 2C):
    O(S * 2W) FLOPs — honest sub-quadratic HLO for gemma3-style local
    layers (vs masking a full S^2 tensor).
    """
    b, s, hq, d = q.shape
    c = window
    assert s % c == 0, (s, c)
    n = s // c
    scale = d ** -0.5
    qc = (q * scale).reshape(b, n, c, hq, d)
    kc = k.reshape(b, n, c, *k.shape[2:])
    vc = v.reshape(b, n, c, *v.shape[2:])
    # previous chunk (zeros for the first chunk — masked out by positions)
    kprev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    kb = jnp.concatenate([kprev, kc], axis=2)                  # (B,n,2C,Hkv,D)
    vb = jnp.concatenate([vprev, vc], axis=2)

    qp = q_positions.reshape(b, n, c)
    kp = k_positions.reshape(b, n, c)
    kp_prev = jnp.concatenate(
        [jnp.full_like(kp[:, :1], -(10 ** 9)), kp[:, :-1]], axis=1)
    kpb = jnp.concatenate([kp_prev, kp], axis=2)               # (B,n,2C)

    kb = _repeat_kv(kb, hq, axis=3)
    vb = _repeat_kv(vb, hq, axis=3)
    scores = jnp.einsum("bnqhd,bnkhd->bnhqk", qc, kb,
                        preferred_element_type=jnp.float32)
    mask = (kpb[:, :, None, None, :] <= qp[:, :, None, :, None])
    mask &= (kpb[:, :, None, None, :] > qp[:, :, None, :, None] - window)
    mask &= (kpb[:, :, None, None, :] >= 0)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(vb.dtype), vb)
    return o.reshape(b, s, hq, d)


# ---------------------------------------------------------------------------
# Attention layer (projections + rope + core dispatch)
# ---------------------------------------------------------------------------
def attention_layer(p: dict, x: jax.Array, positions: jax.Array, *,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    rope_variant: str, rope_theta: float, mrope_sections,
                    window: int = 0, causal: bool = True,
                    chunk_threshold: int = 8192,
                    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
                    kv_positions: Optional[jax.Array] = None,
                    policy: Optional[PrecisionPolicy] = None):
    """Full attention layer on a whole sequence (train / prefill).

    Returns (out, (k, v)) — the K/V tensors are returned so prefill can
    populate the cache.  ``kv_override`` feeds cross-attention.  All
    projections consume params through ``quant_matmul`` — float arrays
    and int8 ``QTensor`` weights take the same call convention.
    """
    b, s, _ = x.shape
    q = quant_matmul(x, p["wq"], policy=policy).reshape(
        b, s, n_heads, head_dim)
    if kv_override is None:
        k = quant_matmul(x, p["wk"], policy=policy).reshape(
            b, s, n_kv_heads, head_dim)
        v = quant_matmul(x, p["wv"], policy=policy).reshape(
            b, s, n_kv_heads, head_dim)
        k_pos = positions if positions.ndim == 2 else positions[..., 0]
        q, k = position_encode(q, k, positions, rope_variant, rope_theta,
                               mrope_sections)
    else:
        k, v = kv_override
        k_pos = kv_positions
        if rope_variant != "none":
            q = (apply_mrope(q, positions, rope_theta, mrope_sections)
                 if rope_variant == "mrope"
                 else apply_rope(q, positions, rope_theta))
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))
    k = constrain(k, ("act_batch", "act_kv_seq", "act_kv_heads", None))
    v = constrain(v, ("act_batch", "act_kv_seq", "act_kv_heads", None))

    q_pos1d = positions if positions.ndim == 2 else positions[..., 0]
    if window > 0 and causal and s % window == 0 and s > window:
        o = local_attention(q, k, v, q_pos1d, k_pos, window)
    elif window > 0 and causal:
        # irregular lengths (smoke shapes): windowed mask on full attention
        o = full_attention(q, k, v, q_pos1d, k_pos, window=window)
    elif k.shape[1] > chunk_threshold and causal:
        o = chunked_attention(q, k, v, q_pos1d, k_pos)
    else:
        o = full_attention(q, k, v, q_pos1d, k_pos, causal=causal)
    o = constrain(o, ("act_batch", "act_seq", "act_heads", None))
    out = quant_matmul(o.reshape(b, s, n_heads * head_dim), p["wo"],
                       policy=policy)
    return out, (k, v)


def attention_decode_layer(p: dict, x: jax.Array, position: jax.Array,
                           cache_k, cache_v,
                           cache_positions: jax.Array, write_idx: jax.Array, *,
                           n_heads: int, n_kv_heads: int, head_dim: int,
                           rope_variant: str, rope_theta: float,
                           mrope_sections, window: int = 0,
                           cross: bool = False,
                           policy: Optional[PrecisionPolicy] = None,
                           kv_len: Optional[jax.Array] = None,
                           active: Optional[jax.Array] = None,
                           block_table: Optional[jax.Array] = None,
                           layer: Optional[jax.Array] = None):
    """One decode step.  x: (B, 1, d); position: (B,) absolute position;
    write_idx: (B,) slot to write KV into (ring index for sliding caches).

    ``cache_k``/``cache_v`` are float arrays or ``Int8KV`` pairs; int8
    caches get the new K/V quantized per (entry, head) on write and
    dequantized tile-by-tile inside the attention kernel — the decode
    path never materializes a float copy of the cache.  A fake_quant
    policy mirrors the numerics bit-exactly on a float cache (quantize→
    dequantize at write time), which is what makes int8 serving testable
    token-exact.

    ``kv_len`` (B,) optionally bounds each row's valid cache region by
    index (the serving tier's per-slot high-water mark); sliding-window
    ring caches derive their own bound from ``position`` (ring fill is a
    prefix of length min(position + 1, window)).

    ``active`` (B,) bool optionally predicates the cache writes: rows
    with ``active == False`` (idle serving slots, and slots mid-chunked-
    prefill) write their *existing* entry back, so a decode step can
    never scribble into a row another phase owns.  ``None`` writes
    unconditionally (single-sequence decode).

    ``block_table`` (B, n_blocks) switches to the **paged pool** layout
    (docs/paged_kv.md): ``cache_k``/``cache_v`` are (NB, BS, Hkv, D)
    pools (Int8KV scales (NB, BS, Hkv)), ``cache_positions`` is the
    (NB, BS) position pool, and this token's KV scatters into physical
    row ``(block_table[b, position // BS], position % BS)`` — inactive
    rows are routed out of bounds and dropped.  The scheduler owns the
    invariant that a written block has refcount 1 (prefix-shared blocks
    are never write targets), so the scatter targets are unique.  Only
    full (non-ring) self-attention caches are ever paged.  ``layer`` (a
    scalar) marks the pools as the stacked (L, NB, BS, ...) leaves the
    layer scan carries: the row is written at ``[layer, blk, off]`` in
    place and the kernel reads that layer through its index maps.

    Returns (out, new_cache_k, new_cache_v, new_cache_positions).
    """
    b = x.shape[0]
    q = quant_matmul(x, p["wq"], policy=policy).reshape(
        b, 1, n_heads, head_dim)
    if cross:
        # Cross attention: cache holds encoder KV; nothing is written.
        o = decode_attention(q, cache_k, cache_v,
                             jnp.full((b,), 2 ** 30, jnp.int32),
                             cache_positions)
        out = quant_matmul(o.reshape(b, 1, n_heads * head_dim), p["wo"],
                           policy=policy)
        return out, cache_k, cache_v, cache_positions
    k = quant_matmul(x, p["wk"], policy=policy).reshape(
        b, 1, n_kv_heads, head_dim)
    v = quant_matmul(x, p["wv"], policy=policy).reshape(
        b, 1, n_kv_heads, head_dim)
    if rope_variant == "mrope":
        pos3 = jnp.broadcast_to(position[:, None, None], (b, 1, 3))
        q = apply_mrope(q, pos3, rope_theta, mrope_sections)
        k = apply_mrope(k, pos3, rope_theta, mrope_sections)
    elif rope_variant == "rope":
        q = apply_rope(q, position[:, None], rope_theta)
        k = apply_rope(k, position[:, None], rope_theta)

    if block_table is not None:
        # Paged pool: this token's row lives at (table[b, pos // BS],
        # pos % BS).  Inactive rows scatter out of bounds → dropped.
        nb, bs = cache_positions.shape
        blk = jnp.take_along_axis(
            block_table, (write_idx // bs)[:, None], axis=1)[:, 0]
        off = write_idx % bs
        if active is not None:
            blk = jnp.where(active, blk, nb)
        kv_at = (blk, off) if layer is None else (layer, blk, off)

        def upd(cache, new):
            # new: (B, 1, ...) — one row per slot, unique (blk, off)
            # targets by the refcount-1 write invariant
            return cache.at[kv_at].set(new[:, 0].astype(cache.dtype),
                                       mode="drop")
        # one (NB, BS) position pool serves every layer
        cache_positions = cache_positions.at[blk, off].set(position,
                                                           mode="drop")
    else:
        def upd(cache, new):
            if active is None:
                return jax.vmap(
                    lambda c, n, i: lax.dynamic_update_slice_in_dim(
                        c, n, i, axis=0)
                )(cache, new, write_idx)

            def one(c, n, i, a):
                old = lax.dynamic_slice_in_dim(c, i, n.shape[0], axis=0)
                return lax.dynamic_update_slice_in_dim(
                    c, jnp.where(a, n, old), i, axis=0)
            return jax.vmap(one)(cache, new, write_idx, active)
        cache_positions = upd(cache_positions, position[:, None])

    if isinstance(cache_k, Int8KV):
        qk, qv = quant_kv(k), quant_kv(v)
        cache_k = Int8KV(upd(cache_k.q, qk.q), upd(cache_k.scale, qk.scale))
        cache_v = Int8KV(upd(cache_v.q, qv.q), upd(cache_v.scale, qv.scale))
    else:
        if (policy is not None and policy.kv_cache == "int8"
                and policy.compute == "fake_quant"):
            k = dequant_kv(quant_kv(k), k.dtype)
            v = dequant_kv(quant_kv(v), v.dtype)
        cache_k = upd(cache_k, k)
        cache_v = upd(cache_v, v)
    cache_k = _constrain_decode_kv(cache_k)
    cache_v = _constrain_decode_kv(cache_v)
    s_kv = cache_positions.shape[1]
    if window > 0:
        # Ring cache: slots 0..min(position, w-1) are the only ones ever
        # written (slot = pos % w), so the fill is a prefix the kernel
        # can bound on; kv_len == 0 (an idle serving slot) still wins.
        bound = jnp.minimum(position.astype(jnp.int32) + 1, s_kv)
        if kv_len is not None:
            bound = jnp.minimum(bound, jnp.clip(kv_len, 0, s_kv))
    else:
        bound = kv_len
    o = decode_attention(q, cache_k, cache_v, position,
                         cache_positions, window=window, kv_len=bound,
                         block_table=block_table, layer=layer)
    out = quant_matmul(o.reshape(b, 1, n_heads * head_dim), p["wo"],
                       policy=policy)
    return out, cache_k, cache_v, cache_positions


def ring_scatter_idx(positions: jax.Array, window: int) -> jax.Array:
    """Ring write targets for a prefill chunk.  positions: (B, C)
    absolute chunk positions (−1 pad).  Returns (B, C) scatter indices
    into a ``window``-row ring: entry i lands at ``pos % window``; pad
    entries and entries older than the chunk's last ``window`` real
    tokens (which would collide with a newer in-chunk winner) are routed
    to index ``window`` — out of bounds, dropped by the scatter.
    """
    b, c = positions.shape
    valid = positions >= 0
    n_valid = valid.sum(axis=1, keepdims=True)               # (B, 1)
    i = jnp.broadcast_to(jnp.arange(c, dtype=positions.dtype)[None, :],
                         (b, c))
    winner = valid & (i >= n_valid - window)
    return jnp.where(winner, positions % window, window).astype(jnp.int32)


def _ring_scatter(cache: jax.Array, new: jax.Array, idx: jax.Array):
    """Per-row scatter of chunk entries into a ring cache.  cache:
    (B, w, ...), new: (B, C, ...), idx: (B, C) with out-of-bounds ==
    dropped (see ``ring_scatter_idx``)."""
    return jax.vmap(lambda c, n, i: c.at[i].set(n.astype(c.dtype)))(
        cache, new, idx)


def attention_chunk_layer(p: dict, x: jax.Array, positions: jax.Array,
                          cache_k, cache_v,
                          cache_positions: jax.Array, write_idx: jax.Array, *,
                          n_heads: int, n_kv_heads: int, head_dim: int,
                          rope_variant: str, rope_theta: float,
                          mrope_sections, window: int = 0,
                          cross: bool = False,
                          policy: Optional[PrecisionPolicy] = None,
                          kv_len: Optional[jax.Array] = None,
                          block_table: Optional[jax.Array] = None,
                          layer: Optional[jax.Array] = None):
    """One chunk-prefill step: C tokens written unpadded into the slot's
    cache rows, attending over the slot's live KV prefix plus themselves.

    x: (B, C, d); positions: (B, C) absolute positions, −1 marking the
    pad tail of a ragged final chunk (pad entries are written with
    position −1 — invalid — and their outputs are discarded).

    * ``window == 0`` (full/global cache): the chunk's K/V is written at
      rows ``[write_idx, write_idx + C)`` *first*, then the chunk queries
      attend the cache bounded by ``kv_len`` (the post-write fill) — the
      rows ahead of the fill are dead by the slot contract, so the write
      is safe and in-chunk causality is pure position masking.
    * ``window > 0`` (ring cache): writing first would let early chunk
      entries overwrite ring history late queries still need, so the
      chunk attends ``[ring cache ∥ chunk]`` concatenated, then the last
      ``window`` real entries are scattered into their ``pos % window``
      slots (older ones can never be attended again).

    Int8KV caches quantize the chunk per (entry, head) before the write/
    concat — the fake-quant policy mirrors the round-trip in float, which
    is what keeps int8 chunked serving testable token-exact.

    ``block_table`` (B, n_blocks) switches the ``window == 0`` path to
    the paged-pool layout (docs/paged_kv.md): the chunk's C rows scatter
    into physical rows ``(table[b, (p + i) // BS], (p + i) % BS)`` —
    pad-tail rows included, stamped position −1, so a recycled block can
    never leak a stale position inside the post-write fill — and the
    attention resolves through the same table in the kernel index maps.
    ``layer`` marks stacked pools exactly as in ``attention_decode_layer``.

    Returns (out (B, C, d), new_cache_k, new_cache_v, new_cache_positions).
    """
    b, c, _ = x.shape
    q = quant_matmul(x, p["wq"], policy=policy).reshape(
        b, c, n_heads, head_dim)
    if cross:
        # Cross attention: cache holds encoder KV; nothing is written and
        # every (non-pad) query may attend every encoder entry.
        if rope_variant != "none":
            q = (apply_mrope(q, jnp.broadcast_to(positions[..., None],
                                                 (b, c, 3)),
                             rope_theta, mrope_sections)
                 if rope_variant == "mrope"
                 else apply_rope(q, positions, rope_theta))
        q_valid = jnp.where(positions >= 0, 2 ** 30, -1)
        o = chunk_attention(q, cache_k, cache_v, q_valid, cache_positions)
        out = quant_matmul(o.reshape(b, c, n_heads * head_dim), p["wo"],
                           policy=policy)
        return out, cache_k, cache_v, cache_positions
    k = quant_matmul(x, p["wk"], policy=policy).reshape(
        b, c, n_kv_heads, head_dim)
    v = quant_matmul(x, p["wv"], policy=policy).reshape(
        b, c, n_kv_heads, head_dim)
    if rope_variant == "mrope":
        pos3 = jnp.broadcast_to(positions[..., None], (b, c, 3))
        q = apply_mrope(q, pos3, rope_theta, mrope_sections)
        k = apply_mrope(k, pos3, rope_theta, mrope_sections)
    elif rope_variant == "rope":
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    if (policy is not None and policy.kv_cache == "int8"
            and policy.compute == "fake_quant"
            and not isinstance(cache_k, Int8KV)):
        k = dequant_kv(quant_kv(k), k.dtype)
        v = dequant_kv(quant_kv(v), v.dtype)

    if window > 0:
        # ring: attend [cache ∥ chunk], then scatter the winners in
        if isinstance(cache_k, Int8KV):
            qk, qv = quant_kv(k), quant_kv(v)
            k_all = Int8KV(jnp.concatenate([cache_k.q, qk.q], axis=1),
                           jnp.concatenate([cache_k.scale, qk.scale],
                                           axis=1))
            v_all = Int8KV(jnp.concatenate([cache_v.q, qv.q], axis=1),
                           jnp.concatenate([cache_v.scale, qv.scale],
                                           axis=1))
        else:
            k_all = jnp.concatenate([cache_k, k.astype(cache_k.dtype)],
                                    axis=1)
            v_all = jnp.concatenate([cache_v, v.astype(cache_v.dtype)],
                                    axis=1)
        pos_all = jnp.concatenate([cache_positions, positions], axis=1)
        o = chunk_attention(q, k_all, v_all, positions, pos_all,
                            window=window)
        idx = ring_scatter_idx(positions, window)
        if isinstance(cache_k, Int8KV):
            cache_k = Int8KV(_ring_scatter(cache_k.q, qk.q, idx),
                             _ring_scatter(cache_k.scale, qk.scale, idx))
            cache_v = Int8KV(_ring_scatter(cache_v.q, qv.q, idx),
                             _ring_scatter(cache_v.scale, qv.scale, idx))
        else:
            cache_k = _ring_scatter(cache_k, k, idx)
            cache_v = _ring_scatter(cache_v, v, idx)
        cache_positions = _ring_scatter(cache_positions, positions, idx)
    else:
        if block_table is not None:
            # Paged pool: row p + i of the chunk scatters into physical
            # (table[b, (p+i) // BS], (p+i) % BS).  Pad-tail rows write
            # too (their position stamp is −1), so no stale tenant
            # position survives inside the post-write fill p + C.
            bs = cache_positions.shape[1]
            tgt = write_idx[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
            blk = jnp.take_along_axis(block_table, tgt // bs, axis=1)
            off = tgt % bs
            kv_at = (blk, off) if layer is None else (layer, blk, off)

            def upd(cache, new):
                # (B, C) index pairs — unique targets per refcount-1
                # write invariant (shared prefix blocks are skipped by
                # the scheduler, never written)
                return cache.at[kv_at].set(new.astype(cache.dtype))
            cache_positions = cache_positions.at[blk, off].set(positions)
        else:
            def upd(cache, new):
                return jax.vmap(
                    lambda cc, n, i: lax.dynamic_update_slice_in_dim(
                        cc, n.astype(cc.dtype), i, axis=0)
                )(cache, new, write_idx)
            cache_positions = upd(cache_positions, positions)

        if isinstance(cache_k, Int8KV):
            qk, qv = quant_kv(k), quant_kv(v)
            cache_k = Int8KV(upd(cache_k.q, qk.q),
                             upd(cache_k.scale, qk.scale))
            cache_v = Int8KV(upd(cache_v.q, qv.q),
                             upd(cache_v.scale, qv.scale))
        else:
            cache_k = upd(cache_k, k)
            cache_v = upd(cache_v, v)
        s_kv = cache_positions.shape[1]
        bound = None if kv_len is None else jnp.clip(kv_len, 0, s_kv)
        if block_table is not None:
            bound = kv_len
        o = chunk_attention(q, cache_k, cache_v, positions,
                            cache_positions, kv_len=bound,
                            block_table=block_table, layer=layer)
    cache_k = _constrain_decode_kv(cache_k)
    cache_v = _constrain_decode_kv(cache_v)
    out = quant_matmul(o.reshape(b, c, n_heads * head_dim), p["wo"],
                       policy=policy)
    return out, cache_k, cache_v, cache_positions


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def swiglu_mlp(p: dict, x: jax.Array,
               policy: Optional[PrecisionPolicy] = None) -> jax.Array:
    gate = quant_matmul(x, p["w_gate"], policy=policy)
    up = quant_matmul(x, p["w_up"], policy=policy)
    h = jax.nn.silu(gate) * up
    h = constrain(h, ("act_batch", "act_seq", "act_ff"))
    return quant_matmul(h, p["w_down"], policy=policy)
