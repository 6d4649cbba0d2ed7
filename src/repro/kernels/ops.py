"""jit'd dispatch wrappers: Pallas kernel on TPU, jnp ref on CPU.

The model layers call these; on the CPU container every graph lowers via
the ref path (so dry-runs/pjit work), while on a real TPU backend the
Pallas kernels take over.  ``force`` pins a path for one call; the
``repro.flags`` level ``kernel_path`` (seeded from $REPRO_KERNEL_PATH)
pins every dispatch suite-wide, so CI can run the whole test matrix
through Pallas interpret mode without touching call sites.

``quant_matmul`` is the precision-aware matmul every dense/projection op
in ``models/`` routes through: plain float arrays take the untouched
``x @ w`` path, ``QTensor`` weights take the dynamic-activation int8
path (or its fake-quant float simulation, per ``PrecisionPolicy``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import flags
from repro.core.quantize import Int8KV, PrecisionPolicy, QTensor, quant_dynamic
from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import int8_matmul as im
from repro.kernels import mamba_scan as ms
from repro.kernels import mel_frontend as mf
from repro.kernels import ref


def resolve_path(force: Optional[str] = None) -> str:
    """Backend for one kernel dispatch: per-call force > flags pin >
    default probe (pallas on TPU, ref elsewhere)."""
    on_tpu = jax.default_backend() == "tpu"
    return force or flags.get("kernel_path") or ("pallas" if on_tpu else "ref")


def int8_matmul(x_q, w_q, x_scale, w_scale, *, force: Optional[str] = None):
    path = resolve_path(force)
    if path == "pallas":
        return im.int8_matmul(x_q, w_q, x_scale, w_scale)
    if path == "interpret":
        return im.int8_matmul(x_q, w_q, x_scale, w_scale, interpret=True)
    return ref.int8_matmul_ref(x_q, w_q, x_scale, w_scale)


def quant_matmul(x: jax.Array, w, *,
                 policy: Optional[PrecisionPolicy] = None,
                 force: Optional[str] = None) -> jax.Array:
    """Precision-aware matmul: ``x (..., K) @ w (K, N)``.

    ``w`` is either a raw float array — the float path, identical to the
    pre-refactor ``x @ w.astype(x.dtype)`` — or a ``QTensor``: the input
    rows are quantized dynamically (or against the QTensor's calibrated
    amax), the int8×int8 kernel runs with dequant fused in its epilogue,
    and the f32 result is cast back to the activation dtype.  With
    ``policy.compute == "fake_quant"`` the same quantization decisions
    run in float: the *integer-valued* f32 matmul with scales applied
    once afterward — the same accumulate-then-scale order as the int8
    kernel, so the simulation is bit-identical to the native path while
    every partial dot product stays inside f32's exact-integer range
    (|sum| < 2^24, guaranteed at worst-case int8 magnitudes for K ≤ 1040
    and true in practice far beyond).  That is the reference the int8
    serving path is tested token-exact against.
    """
    if not isinstance(w, QTensor):
        return x @ w.astype(x.dtype)
    policy = policy or PrecisionPolicy(weights="int8")
    lead, kdim = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, kdim)
    amax = w.amax if policy.activations == "calibrated" else None
    xq, xs = quant_dynamic(x2, amax)
    if policy.compute == "fake_quant":
        acc = xq.astype(jnp.float32) @ w.q.astype(jnp.float32)
        out = acc * (xs[:, None] * w.scale[..., None, :])
    else:
        out = int8_matmul(xq, w.q, xs, w.scale, force=force)
    return out.reshape(*lead, w.q.shape[-1]).astype(x.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    force: Optional[str] = None):
    """q/k/v: (B, S, H, D) — GQA expansion done here; kernel takes (BH,S,D)."""
    path = resolve_path(force)
    if path == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    b, s, h, d = q.shape
    if k.shape[2] != h:
        k = jnp.repeat(k, h // k.shape[2], axis=2)
        v = jnp.repeat(v, h // v.shape[2], axis=2)

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    out = fa.flash_attention(fold(q), fold(k), fold(v), causal=causal,
                             window=window,
                             interpret=(path == "interpret"))
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _kv_operands(k_cache, v_cache, layer, path):
    """(k, v, k_scale, v_scale) of float or ``Int8KV`` caches; the ref
    oracle takes ``layer``'s slice of a stacked pool, the kernels read
    it in place."""
    if isinstance(k_cache, Int8KV):
        out = (k_cache.q, v_cache.q, k_cache.scale, v_cache.scale)
    else:
        out = (k_cache, v_cache, None, None)
    if layer is not None and path == "ref":
        out = tuple(None if x is None else x[layer] for x in out)
    return out


def decode_attention(q, k_cache, v_cache, q_position, cache_positions, *,
                     window: int = 0,
                     kv_len: Optional[jax.Array] = None,
                     block_table: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None,
                     force: Optional[str] = None) -> jax.Array:
    """One-token decode attention against a slot-addressed KV cache.

    q: (B, 1, Hq, D); ``k_cache``/``v_cache``: (B, Skv, Hkv, D) float
    arrays or ``Int8KV`` pairs; q_position: (B,); cache_positions:
    (B, Skv) stored positions, −1 marking invalid entries.

    ``kv_len`` (B,) is the serving tier's per-slot high-water mark: the
    caller guarantees every entry at index >= kv_len[b] is invalid, so
    the kernel skips those blocks outright (capacity is sized for the
    worst case; typical slots fill a fraction of it).  ``None`` means no
    bound (scan the whole cache; masking alone decides validity).

    ``block_table`` (B, n_blocks) int32 switches to the **paged pool**
    layout (docs/paged_kv.md): caches are (NB, BS, Hkv, D) pools of
    fixed-size blocks, ``cache_positions`` is (NB, BS), and logical KV
    block ``j`` of slot ``b`` resolves to physical block
    ``block_table[b, j]`` — inside the Pallas index maps on the kernel
    paths, by an explicit gather through the same table in the ref
    oracle.  ``kv_len`` is then mandatory (it is what fences a slot off
    from the stale blocks its table tail names).  ``layer`` (a scalar)
    marks the pools as stacked (L, NB, BS, ...) and reads that layer:
    in the kernels' index maps, by a slice in the ref oracle.

    Int8 caches are dequantized per tile — inside the Pallas VMEM tile
    on the kernel paths, per ``lax.scan`` block in the ref simulation —
    so decode never materializes a float copy of the cache.
    """
    path = resolve_path(force)
    k, v, k_scale, v_scale = _kv_operands(k_cache, v_cache, layer, path)
    if block_table is not None and kv_len is None:
        raise ValueError("paged decode_attention requires kv_len")
    if path == "ref":
        if block_table is not None:
            return ref.paged_decode_attention_ref(
                q, k, v, q_position, cache_positions, block_table,
                kv_len, window=window, k_scale=k_scale, v_scale=v_scale)
        return ref.decode_attention_ref(
            q, k, v, q_position, cache_positions, window=window,
            kv_len=kv_len, k_scale=k_scale, v_scale=v_scale)
    b, _, hq, d = q.shape
    hkv = k.shape[-2]
    if kv_len is None:
        kv_len = jnp.full((b,), k.shape[1], jnp.int32)
    out = fd.flash_decode(
        q.reshape(b, hkv, hq // hkv, d), k, v,
        q_position.astype(jnp.int32), cache_positions, kv_len,
        k_scale=k_scale, v_scale=v_scale, block_table=block_table,
        layer=layer, window=window, interpret=(path == "interpret"))
    return out.reshape(b, 1, hq, d)


def chunk_attention(q, k_cache, v_cache, q_positions, cache_positions, *,
                    window: int = 0,
                    kv_len: Optional[jax.Array] = None,
                    block_table: Optional[jax.Array] = None,
                    layer: Optional[jax.Array] = None,
                    force: Optional[str] = None) -> jax.Array:
    """Chunk-prefill attention: C query tokens per slot against the
    slot-addressed KV cache (the admission path of chunked pad-free
    prefill; ``decode_attention`` is the C == 1 case).

    q: (B, C, Hq, D); ``k_cache``/``v_cache``: (B, Skv, Hkv, D) float
    arrays or ``Int8KV`` pairs; q_positions: (B, C) absolute positions
    (−1 marks pad queries in a ragged final chunk — their outputs are
    exact zeros, discarded by the caller); cache_positions: (B, Skv).

    The chunk's own KV must already be resident (written into the cache
    rows, or concatenated for ring layouts) — in-chunk causality is pure
    position masking.  ``kv_len`` (B,) is the post-write fill ``p + C``:
    blocks past it are skipped by the kernel exactly as in decode.

    ``block_table`` (B, n_blocks) selects the paged-pool layout exactly
    as in ``decode_attention`` (pool caches, table-resolved index maps /
    ref gather, mandatory ``kv_len``, ``layer`` of a stacked pool).
    """
    path = resolve_path(force)
    k, v, k_scale, v_scale = _kv_operands(k_cache, v_cache, layer, path)
    if block_table is not None and kv_len is None:
        raise ValueError("paged chunk_attention requires kv_len")
    if path == "ref":
        if block_table is not None:
            return ref.paged_chunk_attention_ref(
                q, k, v, q_positions, cache_positions, block_table,
                kv_len, window=window, k_scale=k_scale, v_scale=v_scale)
        return ref.chunk_attention_ref(
            q, k, v, q_positions, cache_positions, window=window,
            kv_len=kv_len, k_scale=k_scale, v_scale=v_scale)
    b, c, hq, d = q.shape
    hkv = k.shape[-2]
    g = hq // hkv
    if kv_len is None:
        kv_len = jnp.full((b,), k.shape[1], jnp.int32)
    # grouped rows ordered (query, group): row c*G + g shares KV head h
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, c * g, d)
    qp_rows = jnp.broadcast_to(q_positions[:, :, None],
                               (b, c, g)).reshape(b, c * g)
    out = fd.flash_chunk_prefill(
        qg, k, v, qp_rows.astype(jnp.int32), cache_positions, kv_len,
        k_scale=k_scale, v_scale=v_scale, block_table=block_table,
        layer=layer, window=window, interpret=(path == "interpret"))
    return out.reshape(b, hkv, c, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, hq, d)


def mamba_scan(x, dt, b_mat, c_mat, a, *, force: Optional[str] = None
               ) -> Tuple[jax.Array, jax.Array]:
    path = resolve_path(force)
    if path == "pallas":
        return ms.mamba_scan(x, dt, b_mat, c_mat, a)
    if path == "interpret":
        return ms.mamba_scan(x, dt, b_mat, c_mat, a, interpret=True)
    return ref.mamba_scan_ref(x, dt, b_mat, c_mat, a)


def mel_frontend(frames, window, dft_cos, dft_sin, mel_fb, *,
                 force: Optional[str] = None):
    """frames: (..., F, L) — leading dims folded into the grid."""
    path = resolve_path(force)
    if path == "ref":
        return ref.mel_frontend_ref(frames, window, dft_cos, dft_sin, mel_fb)
    lead = frames.shape[:-2]
    f, l = frames.shape[-2:]
    # fold leading dims into the frame dim
    flat = frames.reshape((-1, l))
    out = mf.mel_frontend(flat, window, dft_cos, dft_sin, mel_fb,
                          interpret=(path == "interpret"))
    return out.reshape(*lead, f, mel_fb.shape[1]) if lead else out
