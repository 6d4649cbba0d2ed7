"""Decoder-only backbone: dense / MoE / hybrid(Mamba2+shared-attn) / SSM / VLM.

One generic model consumes an ``ArchConfig``.  Depth is always lowered as
``lax.scan`` over stacked per-layer params (grouped scans for
heterogeneous patterns), so HLO size is O(1) in depth and remat policies
apply per scanned body.

Three entry points per arch:
* ``forward_train``   — full-sequence forward + LM loss (microbatch view).
* ``forward_prefill`` — full-sequence forward emitting a decode cache.
* ``forward_decode``  — one token against the cache (serve_step).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro import flags
from repro.core.arch import ArchConfig
from repro.core.quantize import Int8KV, PrecisionPolicy, maybe_quant_kv
from repro.models import ssm as ssm_mod
from repro.models.layers import (attention_chunk_layer,
                                 attention_decode_layer, attention_layer,
                                 ring_scatter_idx, _ring_scatter,
                                 rms_norm, swiglu_mlp)
from repro.models.moe import moe_layer
from repro.models.params import layer_pattern
from repro.sharding.policy import constrain

def maybe_cast_params(params, cfg):
    """bf16_params flag: cast >=2D f32 masters to the activation dtype
    once at step entry, so FSDP all-gathers move bf16 (not f32 masters).
    1D scales / ssm dynamics / QTensor dequant scales stay f32."""
    if not flags.get("bf16_params"):
        return params
    dt = cfg.activation_dtype
    from repro.core.quantize import QTensor

    def cast(leaf):
        if isinstance(leaf, QTensor):
            return leaf
        if leaf.ndim >= 2 and leaf.dtype == jnp.float32:
            return leaf.astype(dt)
        return leaf
    casted = jax.tree.map(cast, params,
                          is_leaf=lambda x: isinstance(x, QTensor))
    # Barrier: without it XLA sinks the convert into the layer scan and
    # the FSDP all-gather still moves the f32 master (measured: zero
    # collective-byte change).  With it, the sharded bf16 copy
    # materializes once and every gather moves half the bytes.
    return jax.lax.optimization_barrier(casted)


REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}


def _maybe_remat(fn, policy: Optional[str]):
    if policy is None or policy == "none":
        return fn
    return jax.checkpoint(fn, policy=REMAT_POLICIES[policy],
                          prevent_cse=False)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_tokens(params, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    table = params["embed"].astype(cfg.activation_dtype)
    x = jnp.take(table, tokens, axis=0)
    if cfg.family != "cnn":
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype) if cfg.name.startswith(
            "gemma") else x
    return constrain(x, ("act_batch", "act_res_seq", "act_dmodel"))


def unembed(params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    table = params.get("unembed", params["embed"])
    logits = jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype))
    return constrain(logits, ("act_batch", "act_seq", "act_vocab"))


def lm_loss(logits: jax.Array, labels: jax.Array, vocab_size: int
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Cross-entropy with padded-vocab masking; labels == -1 are ignored."""
    v_pad = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if v_pad > vocab_size:
        col = lax.broadcasted_iota(jnp.int32, (v_pad,), 0)
        logits = logits + jnp.where(col < vocab_size, 0.0, -1e30)
    valid = labels >= 0
    safe_labels = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (logz - picked) * valid
    n = jnp.maximum(valid.sum(), 1)
    loss = nll.sum() / n
    return loss, {"loss": loss, "tokens": n,
                  "ppl_log": loss}


# ---------------------------------------------------------------------------
# Block bodies
# ---------------------------------------------------------------------------
def _attn_kwargs(cfg: ArchConfig, window: int = 0):
    return dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_variant=cfg.rope_variant,
                rope_theta=cfg.rope_theta, mrope_sections=cfg.mrope_sections,
                window=window)


def dense_block(cfg: ArchConfig, p, x, positions, *, window=0,
                causal=True, collect_kv=False, policy=None):
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    attn_out, kv = attention_layer(p["attn"], h, positions, causal=causal,
                                   policy=policy, **_attn_kwargs(cfg, window))
    x = x + attn_out
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + swiglu_mlp(p["mlp"], h, policy)
    x = constrain(x, ("act_batch", "act_res_seq", "act_dmodel"))
    return (x, kv) if collect_kv else (x, None)


def moe_block(cfg: ArchConfig, p, x, positions, *, collect_kv=False,
              policy=None):
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    attn_out, kv = attention_layer(p["attn"], h, positions, policy=policy,
                                   **_attn_kwargs(cfg))
    x = x + attn_out
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + moe_layer(p["moe"], h, cfg)
    x = constrain(x, ("act_batch", "act_res_seq", "act_dmodel"))
    return (x, kv) if collect_kv else (x, None)


def mamba_block(cfg: ArchConfig, p, x, state=None):
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    fn = (ssm_mod.mamba2_layer if cfg.ssm_variant == "mamba2"
          else ssm_mod.mamba1_layer)
    y, new_state = fn(p["mamba"], h, cfg, state)
    x = x + y
    x = constrain(x, ("act_batch", "act_res_seq", "act_dmodel"))
    return x, new_state


def dense_block_decode(cfg: ArchConfig, p, x, position, cache_k, cache_v,
                       cache_pos, write_idx, *, window=0, policy=None,
                       kv_len=None, active=None, block_table=None,
                       layer=None):
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    attn_out, ck, cv, cp = attention_decode_layer(
        p["attn"], h, position, cache_k, cache_v, cache_pos, write_idx,
        policy=policy, kv_len=kv_len, active=active,
        block_table=block_table, layer=layer, **_attn_kwargs(cfg, window))
    x = x + attn_out
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + swiglu_mlp(p["mlp"], h, policy)
    return x, ck, cv, cp


def moe_block_decode(cfg: ArchConfig, p, x, position, cache_k, cache_v,
                     cache_pos, write_idx, policy=None, kv_len=None,
                     active=None, block_table=None, layer=None):
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    attn_out, ck, cv, cp = attention_decode_layer(
        p["attn"], h, position, cache_k, cache_v, cache_pos, write_idx,
        policy=policy, kv_len=kv_len, active=active,
        block_table=block_table, layer=layer, **_attn_kwargs(cfg))
    x = x + attn_out
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + moe_layer(p["moe"], h, cfg)
    return x, ck, cv, cp


def mamba_block_decode(cfg: ArchConfig, p, x, state, active=None):
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    fn = (ssm_mod.mamba2_decode if cfg.ssm_variant == "mamba2"
          else ssm_mod.mamba1_decode)
    y, new_state = fn(p["mamba"], h, cfg, state)
    if active is not None:
        # idle serving slots keep their state: a decode step must never
        # advance the recurrence of a row another phase (chunked prefill)
        # owns.
        new_state = jax.tree.map(
            lambda n, o: jnp.where(
                active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new_state, state)
    return x + y, new_state


# ---------------------------------------------------------------------------
# Chunk-prefill block bodies (C tokens against the live slot cache)
# ---------------------------------------------------------------------------
def dense_block_chunk(cfg: ArchConfig, p, x, positions, cache_k, cache_v,
                      cache_pos, write_idx, *, window=0, policy=None,
                      kv_len=None, block_table=None, layer=None):
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    attn_out, ck, cv, cp = attention_chunk_layer(
        p["attn"], h, positions, cache_k, cache_v, cache_pos, write_idx,
        policy=policy, kv_len=kv_len, block_table=block_table, layer=layer,
        **_attn_kwargs(cfg, window))
    x = x + attn_out
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + swiglu_mlp(p["mlp"], h, policy)
    return x, ck, cv, cp


def moe_block_chunk(cfg: ArchConfig, p, x, positions, cache_k, cache_v,
                    cache_pos, write_idx, policy=None, kv_len=None,
                    block_table=None, layer=None):
    h = rms_norm(p["attn_norm"], x, cfg.norm_eps)
    attn_out, ck, cv, cp = attention_chunk_layer(
        p["attn"], h, positions, cache_k, cache_v, cache_pos, write_idx,
        policy=policy, kv_len=kv_len, block_table=block_table, layer=layer,
        **_attn_kwargs(cfg))
    x = x + attn_out
    h = rms_norm(p["mlp_norm"], x, cfg.norm_eps)
    x = x + moe_layer(p["moe"], h, cfg)
    return x, ck, cv, cp


def mamba_block_chunk(cfg: ArchConfig, p, x, state, mask, fill):
    h = rms_norm(p["norm"], x, cfg.norm_eps)
    fn = (ssm_mod.mamba2_layer if cfg.ssm_variant == "mamba2"
          else ssm_mod.mamba1_layer)
    y, new_state = fn(p["mamba"], h, cfg, state, mask=mask, fill=fill)
    x = x + y
    return x, new_state


# ---------------------------------------------------------------------------
# Trunk (pattern-dispatched scans)
# ---------------------------------------------------------------------------
def _pool_scan(step, x, xs, pools, paged: bool):
    """Scan ``step(h, xs_l, pools_l, layer) -> (h, ys_l, pools_l)`` over
    the leading (layer or group) axis of ``xs`` and of the attention
    caches ``pools``; returns ``(x, ys, pools)``.

    Slot-addressed caches ride as ``xs``/``ys``: the step gets one
    layer's slice and ``layer`` is None.  Paged pools ride whole in the
    carry: the step writes its rows at ``[layer, blk, off]`` and the
    kernel reads the layer through its index maps, so the donated pool
    is updated in place.  As ``xs``/``ys`` they would cost a copy of
    each whole pool before the loop (``ys`` may not overwrite the ``xs``
    the loop still reads), a per-layer slice for the Pallas call (which
    cannot read through a dynamic-slice) and a write of that slice back,
    where a step writes a few rows (docs/paged_kv.md).
    """
    if not paged:
        def body(h, a):
            x_l, pools_l = a
            h, y, pools_l = step(h, x_l, pools_l, None)
            return h, (y, pools_l)
        x, (ys, pools) = lax.scan(body, x, (xs, pools))
        return x, ys, pools

    def carried(carry, a):
        h, pools_c = carry
        x_l, layer = a
        h, y, pools_c = step(h, x_l, pools_c, layer)
        return (h, pools_c), y
    n = jax.tree.leaves(pools)[0].shape[0]
    (x, pools), ys = lax.scan(carried, (x, pools),
                              (xs, jnp.arange(n, dtype=jnp.int32)))
    return x, ys, pools


def trunk_forward(cfg: ArchConfig, params, x, positions, *,
                  remat: str = "none", collect_cache: bool = False,
                  policy: Optional[PrecisionPolicy] = None):
    """Run all blocks.  Returns (x, cache_entries | None)."""
    pat = layer_pattern(cfg)
    caches: Dict[str, jax.Array] = {}

    if pat["kind"] in ("uniform_dense", "uniform_moe"):
        is_moe = pat["kind"] == "uniform_moe"

        def body(h, p):
            fn = moe_block if is_moe else dense_block
            h, kv = fn(cfg, p, h, positions, collect_kv=collect_cache,
                       policy=policy)
            return h, kv
        body = _maybe_remat(body, remat)
        x, kvs = lax.scan(body, x, params["blocks"])
        if collect_cache and kvs is not None:
            caches["k"], caches["v"] = kvs

    elif pat["kind"] == "uniform_ssm":
        def body(h, p):
            h, st = mamba_block(cfg, p, h)
            return h, st if collect_cache else None
        body = _maybe_remat(body, remat)
        x, states = lax.scan(body, x, params["blocks"])
        if collect_cache:
            caches["ssm"] = states

    elif pat["kind"] == "local_global":
        w = cfg.sliding_window

        def local_body(h, p):
            h, kv = dense_block(cfg, p, h, positions, window=w,
                                collect_kv=collect_cache, policy=policy)
            return h, kv

        def group_body(h, p):
            h, local_kv = lax.scan(_maybe_remat(local_body, remat),
                                   h, p["local"])
            h, global_kv = _maybe_remat(
                lambda hh, pp: dense_block(cfg, pp, hh, positions,
                                           collect_kv=collect_cache,
                                           policy=policy),
                remat)(h, p["global"])
            return h, (local_kv, global_kv)

        x, (local_kvs, global_kvs) = lax.scan(
            group_body, x,
            {"local": params["groups"]["local"],
             "global": params["groups"]["global"]})
        if "tail_local" in params:
            x, tail_kvs = lax.scan(_maybe_remat(local_body, remat), x,
                                   params["tail_local"])
        else:
            tail_kvs = None
        if collect_cache:
            caches["local_k"], caches["local_v"] = local_kvs
            caches["global_k"], caches["global_v"] = global_kvs
            if tail_kvs is not None:
                caches["tail_k"], caches["tail_v"] = tail_kvs

    elif pat["kind"] == "hybrid":
        shared = params["shared_attn"]

        def mamba_body(h, p):
            h, st = mamba_block(cfg, p, h)
            return h, st if collect_cache else None

        def group_body(h, p):
            h, states = lax.scan(_maybe_remat(mamba_body, remat), h, p)
            h, kv = _maybe_remat(
                lambda hh, pp: dense_block(cfg, pp, hh, positions,
                                           collect_kv=collect_cache,
                                           policy=policy),
                remat)(h, shared)
            return h, (states, kv)

        x, (states, kvs) = lax.scan(group_body, x, params["groups"])
        if collect_cache:
            caches["ssm"] = states
            caches["attn_k"], caches["attn_v"] = kvs
    else:
        raise ValueError(pat)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, (caches if collect_cache else None)


def trunk_decode(cfg: ArchConfig, params, x, position, cache, *,
                 write_full, write_local,
                 policy: Optional[PrecisionPolicy] = None,
                 kv_len: Optional[jax.Array] = None,
                 active: Optional[jax.Array] = None,
                 block_table: Optional[jax.Array] = None):
    """One-token pass through all blocks, updating the cache pytree.

    ``kv_len`` (B,) is the per-row high-water mark of the full-attention
    caches (serving passes each slot's fill so the decode kernel skips
    the unused capacity tail); ring caches bound themselves from
    ``position``.  ``active`` (B,) bool predicates every cache/state
    write — inactive rows (idle slots, slots mid-chunked-prefill) come
    through the step bit-identical.

    ``block_table`` (B, n_blocks) marks the cache as **paged**: the
    full-attention KV leaves are block pools addressed through the table
    (positions in ``cache["pool_pos"]``) and carried whole through the
    layer scan, updated in place (``_pool_scan``), while sliding-window
    ring caches and SSM state stay slot-addressed — they are O(window) /
    O(state) per slot already, there is no capacity tail to reclaim
    (docs/paged_kv.md).
    """
    pat = layer_pattern(cfg)
    new_cache = dict(cache)
    paged = block_table is not None
    # paged caches keep full-attention positions in the (NB, BS) pool
    full_pos = cache["pool_pos" if paged else "full_pos"] \
        if pat["kind"] != "uniform_ssm" else None

    if pat["kind"] in ("uniform_dense", "uniform_moe"):
        is_moe = pat["kind"] == "uniform_moe"

        def body(h, p, kv, layer):
            fn = moe_block_decode if is_moe else dense_block_decode
            h, ck, cv, _ = fn(cfg, p, h, position, *kv,
                              full_pos, write_full, policy=policy,
                              kv_len=kv_len, active=active,
                              block_table=block_table, layer=layer)
            return h, None, (ck, cv)
        x, _, (new_cache["k"], new_cache["v"]) = _pool_scan(
            body, x, params["blocks"], (cache["k"], cache["v"]), paged)

    elif pat["kind"] == "uniform_ssm":
        def body(h, pc):
            p, st = pc
            h, st = mamba_block_decode(cfg, p, h, ssm_mod.SSMState(*st),
                                       active=active)
            return h, tuple(st)
        x, states = lax.scan(body, x, (params["blocks"],
                                       tuple(cache["ssm"])))
        new_cache["ssm"] = ssm_mod.SSMState(*states)

    elif pat["kind"] == "local_global":
        w = cfg.sliding_window

        def local_body(h, pc):
            p, ck, cv = pc
            h, ck, cv, cp = dense_block_decode(
                cfg, p, h, position, ck, cv, cache["local_pos"],
                write_local, window=w, policy=policy, kv_len=kv_len,
                active=active)
            return h, (ck, cv)

        def group_body(h, xs_l, gkv, layer):
            p, lk, lv = xs_l
            h, (lks, lvs) = lax.scan(local_body, h, (p["local"], lk, lv))
            h, gk, gv, _ = dense_block_decode(
                cfg, p["global"], h, position, *gkv,
                full_pos, write_full, policy=policy, kv_len=kv_len,
                active=active, block_table=block_table, layer=layer)
            return h, (lks, lvs), (gk, gv)

        x, (lks, lvs), (gks, gvs) = _pool_scan(
            group_body, x,
            (params["groups"], cache["local_k"], cache["local_v"]),
            (cache["global_k"], cache["global_v"]), paged)
        new_cache.update(local_k=lks, local_v=lvs,
                         global_k=gks, global_v=gvs)
        if "tail_k" in cache:
            x, (tks, tvs) = lax.scan(
                local_body, x,
                (params["tail_local"], cache["tail_k"], cache["tail_v"]))
            new_cache.update(tail_k=tks, tail_v=tvs)

    elif pat["kind"] == "hybrid":
        shared = params["shared_attn"]

        def mamba_body(h, pc):
            p, st = pc
            h, st = mamba_block_decode(cfg, p, h, ssm_mod.SSMState(*st),
                                       active=active)
            return h, tuple(st)

        def group_body(h, pst, kv, layer):
            p, st = pst
            h, states = lax.scan(mamba_body, h, (p, st))
            h, ck, cv, _ = dense_block_decode(
                cfg, shared, h, position, *kv,
                full_pos, write_full, policy=policy, kv_len=kv_len,
                active=active, block_table=block_table, layer=layer)
            return h, states, (ck, cv)

        x, states, (new_cache["attn_k"], new_cache["attn_v"]) = _pool_scan(
            group_body, x, (params["groups"], tuple(cache["ssm"])),
            (cache["attn_k"], cache["attn_v"]), paged)
        new_cache["ssm"] = ssm_mod.SSMState(*states)
    else:
        raise ValueError(pat)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, new_cache


# ---------------------------------------------------------------------------
# Positions
# ---------------------------------------------------------------------------
def default_positions(cfg: ArchConfig, batch: int, seq: int) -> jax.Array:
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    if cfg.rope_variant == "mrope":
        return jnp.broadcast_to(pos[..., None], (batch, seq, 3))
    return pos


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------
def forward_train(cfg: ArchConfig, params, inputs: Dict[str, jax.Array], *,
                  remat: str = "full",
                  policy: Optional[PrecisionPolicy] = None):
    """inputs: tokens (B,S) int32 OR embeddings (B,S,d); labels (B,S)."""
    params = maybe_cast_params(params, cfg)
    if "embeddings" in inputs:
        x = inputs["embeddings"].astype(cfg.activation_dtype)
        x = constrain(x, ("act_batch", "act_res_seq", "act_dmodel"))
        b, s = x.shape[:2]
    else:
        tokens = inputs["tokens"]
        b, s = tokens.shape
        x = embed_tokens(params, tokens, cfg)
    positions = inputs.get("positions")
    if positions is None:
        positions = default_positions(cfg, b, s)
    x, _ = trunk_forward(cfg, params, x, positions, remat=remat,
                         policy=policy)
    logits = unembed(params, x, cfg)
    return lm_loss(logits, inputs["labels"], cfg.vocab_size)


def forward_prefill(cfg: ArchConfig, params, inputs: Dict[str, jax.Array],
                    policy: Optional[PrecisionPolicy] = None):
    """Returns (last_token_logits, cache).  ``policy`` selects the KV
    cache representation (float / Int8KV / fake-quant float) and the
    matmul compute mode for QTensor params."""
    params = maybe_cast_params(params, cfg)
    if "embeddings" in inputs:
        x = inputs["embeddings"].astype(cfg.activation_dtype)
        b, s = x.shape[:2]
    else:
        tokens = inputs["tokens"]
        b, s = tokens.shape
        x = embed_tokens(params, tokens, cfg)
    positions = inputs.get("positions")
    if positions is None:
        positions = default_positions(cfg, b, s)
    x, caches = trunk_forward(cfg, params, x, positions, collect_cache=True,
                              policy=policy)
    logits = unembed(params, x[:, -1:, :], cfg)[:, 0]
    cache = _cache_from_prefill(cfg, caches, positions, b, s, policy=policy)
    return logits, cache


def forward_decode(cfg: ArchConfig, params, cache, token: jax.Array,
                   position: jax.Array, write_idx: Optional[jax.Array] = None,
                   policy: Optional[PrecisionPolicy] = None,
                   kv_len: Optional[jax.Array] = None,
                   block_table: Optional[jax.Array] = None):
    """token: (B,) int32; position: (B,) absolute index of this token.

    ``write_idx`` (B,) is the cache slot row index to write KV into; it
    defaults to ``position``, which is also what the serving engine uses
    — pad-free chunked admission keeps every cache row contiguous in
    positions, so index == position always.  (The override remains for
    callers with exotic layouts.)  Attention validity is always decided
    by stored positions, never by slot index.

    ``kv_len`` (B,) optionally bounds each row's live cache region by
    index: the caller promises every entry at index >= kv_len is invalid
    (position −1), letting the decode kernel skip the capacity tail.
    ``kv_len == 0`` marks an idle serving slot: its row is skipped by the
    kernel AND every cache/state write for it is suppressed — the step
    cannot scribble into a row the scheduler has parked or is chunk-
    prefilling.  ``None`` scans (and writes) the whole cache — masking
    alone still guarantees correctness.

    ``block_table`` (B, n_blocks) marks ``cache`` as a **paged** decode
    cache (full-attention KV block pools + ``pool_pos``; ring/SSM leaves
    slot-addressed as ever — see docs/paged_kv.md); ``kv_len`` is then
    required and the write lands in the physical block the table names.
    """
    params = maybe_cast_params(params, cfg)
    x = embed_tokens(params, token[:, None], cfg)
    w = cfg.sliding_window
    write_full = position if write_idx is None else write_idx
    write_local = position % w if w else write_full
    active = None if kv_len is None else kv_len > 0
    x, new_cache = trunk_decode(cfg, params, x, position, cache,
                                write_full=write_full,
                                write_local=write_local, policy=policy,
                                kv_len=kv_len, active=active,
                                block_table=block_table)
    logits = unembed(params, x, cfg)[:, 0]
    # position bookkeeping lives outside trunk_decode (shared across layers)
    if "pool_pos" in new_cache:
        new_cache["pool_pos"] = _write_pool_pos(
            new_cache["pool_pos"], position[:, None], write_full,
            block_table, active)
    elif "full_pos" in new_cache:
        new_cache["full_pos"] = _write_pos(new_cache["full_pos"], position,
                                           write_full, active)
    if "local_pos" in new_cache:
        new_cache["local_pos"] = _write_pos(new_cache["local_pos"], position,
                                            write_local, active)
    return logits, new_cache


def _write_pos(pos_arr, position, idx, active=None):
    if active is None:
        return jax.vmap(
            lambda cp, pv, i: lax.dynamic_update_slice_in_dim(cp, pv[None],
                                                              i, 0)
        )(pos_arr, position, idx)

    def one(cp, pv, i, a):
        old = lax.dynamic_slice_in_dim(cp, i, 1, 0)
        return lax.dynamic_update_slice_in_dim(
            cp, jnp.where(a, pv[None], old), i, 0)
    return jax.vmap(one)(pos_arr, position, idx, active)


def _write_pos_chunk(pos_arr, positions, idx):
    """Stamp a whole chunk's (B, C) positions at per-row offset ``idx``
    — the multi-entry sibling of ``_write_pos`` (pad tail entries carry
    −1 and are written invalid)."""
    return jax.vmap(
        lambda cp, pv, i: lax.dynamic_update_slice_in_dim(cp, pv, i, 0)
    )(pos_arr, positions, idx)


def _write_pool_pos(pool_pos, positions, write_idx, block_table,
                    active=None):
    """Paged sibling of ``_write_pos``/``_write_pos_chunk``: stamp (B, C)
    positions into the (NB, BS) position pool at logical rows
    ``[write_idx, write_idx + C)`` resolved through ``block_table``;
    rows with ``active == False`` are routed out of bounds and dropped.
    Pad entries (position −1) are stamped too — that is what keeps a
    recycled physical block free of stale tenant positions inside the
    post-write fill."""
    nb, bs = pool_pos.shape
    c = positions.shape[1]
    tgt = write_idx[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
    blk = jnp.take_along_axis(block_table, tgt // bs, axis=1)
    if active is not None:
        blk = jnp.where(active[:, None], blk, nb)
    return pool_pos.at[blk, tgt % bs].set(positions, mode="drop")


# ---------------------------------------------------------------------------
# Chunked pad-free prefill (serving admission path)
# ---------------------------------------------------------------------------
def trunk_prefill_chunk(cfg: ArchConfig, params, x, positions, cache, *,
                        write_full,
                        policy: Optional[PrecisionPolicy] = None,
                        kv_len: Optional[jax.Array] = None,
                        block_table: Optional[jax.Array] = None):
    """C-token pass through all blocks against the live slot cache.

    The chunk sibling of ``trunk_decode``: attention layers write the
    chunk's KV unpadded into rows ``[write_full, write_full + C)`` (ring
    layers scatter at ``pos % window``) and attend the slot's live
    prefix plus the chunk; SSM layers advance the carried recurrent
    state over exactly the chunk's real tokens (pad steps of a ragged
    final chunk are exact no-ops).

    ``block_table`` (B, n_blocks) marks the cache as paged, exactly as
    in ``trunk_decode`` (full-attention leaves are block pools, ring /
    SSM leaves stay slot-addressed).
    """
    pat = layer_pattern(cfg)
    new_cache = dict(cache)
    mask = positions >= 0
    fill = mask.sum(axis=1).astype(jnp.int32)
    paged = block_table is not None
    full_pos = cache["pool_pos" if paged else "full_pos"] \
        if pat["kind"] != "uniform_ssm" else None

    if pat["kind"] in ("uniform_dense", "uniform_moe"):
        is_moe = pat["kind"] == "uniform_moe"

        def body(h, p, kv, layer):
            fn = moe_block_chunk if is_moe else dense_block_chunk
            h, ck, cv, _ = fn(cfg, p, h, positions, *kv,
                              full_pos, write_full, policy=policy,
                              kv_len=kv_len, block_table=block_table,
                              layer=layer)
            return h, None, (ck, cv)
        x, _, (new_cache["k"], new_cache["v"]) = _pool_scan(
            body, x, params["blocks"], (cache["k"], cache["v"]), paged)

    elif pat["kind"] == "uniform_ssm":
        def body(h, pc):
            p, st = pc
            h, st = mamba_block_chunk(cfg, p, h, ssm_mod.SSMState(*st),
                                      mask, fill)
            return h, tuple(st)
        x, states = lax.scan(body, x, (params["blocks"],
                                       tuple(cache["ssm"])))
        new_cache["ssm"] = ssm_mod.SSMState(*states)

    elif pat["kind"] == "local_global":
        w = cfg.sliding_window

        def local_body(h, pc):
            p, ck, cv = pc
            h, ck, cv, cp = dense_block_chunk(
                cfg, p, h, positions, ck, cv, cache["local_pos"],
                write_full, window=w, policy=policy, kv_len=kv_len)
            return h, (ck, cv)

        def group_body(h, xs_l, gkv, layer):
            p, lk, lv = xs_l
            h, (lks, lvs) = lax.scan(local_body, h, (p["local"], lk, lv))
            h, gk, gv, _ = dense_block_chunk(
                cfg, p["global"], h, positions, *gkv,
                full_pos, write_full, policy=policy, kv_len=kv_len,
                block_table=block_table, layer=layer)
            return h, (lks, lvs), (gk, gv)

        x, (lks, lvs), (gks, gvs) = _pool_scan(
            group_body, x,
            (params["groups"], cache["local_k"], cache["local_v"]),
            (cache["global_k"], cache["global_v"]), paged)
        new_cache.update(local_k=lks, local_v=lvs,
                         global_k=gks, global_v=gvs)
        if "tail_k" in cache:
            x, (tks, tvs) = lax.scan(
                local_body, x,
                (params["tail_local"], cache["tail_k"], cache["tail_v"]))
            new_cache.update(tail_k=tks, tail_v=tvs)

    elif pat["kind"] == "hybrid":
        shared = params["shared_attn"]

        def mamba_body(h, pc):
            p, st = pc
            h, st = mamba_block_chunk(cfg, p, h, ssm_mod.SSMState(*st),
                                      mask, fill)
            return h, tuple(st)

        def group_body(h, pst, kv, layer):
            p, st = pst
            h, states = lax.scan(mamba_body, h, (p, st))
            h, ck, cv, _ = dense_block_chunk(
                cfg, shared, h, positions, *kv,
                full_pos, write_full, policy=policy, kv_len=kv_len,
                block_table=block_table, layer=layer)
            return h, states, (ck, cv)

        x, states, (new_cache["attn_k"], new_cache["attn_v"]) = _pool_scan(
            group_body, x, (params["groups"], tuple(cache["ssm"])),
            (cache["attn_k"], cache["attn_v"]), paged)
        new_cache["ssm"] = ssm_mod.SSMState(*states)
    else:
        raise ValueError(pat)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, new_cache


def forward_prefill_chunk(cfg: ArchConfig, params, cache,
                          tokens: jax.Array, positions: jax.Array,
                          policy: Optional[PrecisionPolicy] = None,
                          kv_len: Optional[jax.Array] = None,
                          block_table: Optional[jax.Array] = None):
    """One fixed-size prefill chunk against a live slot cache.

    tokens: (B, C) int32; positions: (B, C) absolute positions — the
    chunk covers ``[p, p + C)`` of its prompt with ``p = positions[:, 0]``
    (the first entry is always a real token); a ragged final chunk pads
    the tail with position −1 (pad rows are written invalid and their
    logits are garbage the caller must ignore).

    ``kv_len`` (B,) is the post-write fill ``p + C`` bounding the
    attention sweep (``None`` scans the whole capacity; stored positions
    still decide validity).  Returns (logits (B, C, vocab), new_cache):
    the caller reads the next token from the last *real* row's logits.

    Calling this ceil(S / C) times over a prompt of length S reproduces
    ``forward_prefill``'s cache and final-token logits without a single
    pad row entering the KV cache or the SSM recurrence — the admission
    path of the chunked continuous-batching engine.
    """
    params = maybe_cast_params(params, cfg)
    x = embed_tokens(params, tokens, cfg)
    w = cfg.sliding_window
    write_full = positions[:, 0]
    x, new_cache = trunk_prefill_chunk(cfg, params, x, positions, cache,
                                       write_full=write_full, policy=policy,
                                       kv_len=kv_len,
                                       block_table=block_table)
    logits = unembed(params, x, cfg)
    # position bookkeeping outside the trunk (shared across layers)
    if "pool_pos" in new_cache:
        new_cache["pool_pos"] = _write_pool_pos(
            new_cache["pool_pos"], positions, write_full, block_table)
    elif "full_pos" in new_cache:
        new_cache["full_pos"] = _write_pos_chunk(new_cache["full_pos"],
                                                 positions, write_full)
    if "local_pos" in new_cache:
        idx = ring_scatter_idx(positions, w)
        new_cache["local_pos"] = _ring_scatter(new_cache["local_pos"],
                                               positions, idx)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------
def _ring_select(pos1d: jax.Array, w: int):
    """Per-row ring placement for sliding-window caches.

    pos1d: (B, S) absolute positions, −1 marking invalid (pad) entries.
    The ring keeps, per row, the w most-recent *real* entries at slot
    ``pos % w``.  Returns (src, has, local_pos): source index into S
    per ring slot, slot validity, and the stored position per slot
    (−1 when empty) — per-row, so padded batches with different pad
    widths per sequence stay correct.
    """
    max_pos = jnp.max(pos1d, axis=1, keepdims=True)            # (B, 1)
    keep = (pos1d >= 0) & (pos1d > max_pos - w)                # (B, S)
    slot_of = jnp.where(keep, pos1d % w, w)                    # w = "none"
    slot_ids = jnp.arange(w, dtype=pos1d.dtype)[None, :, None]
    match = slot_of[:, None, :] == slot_ids                    # (B, w, S)
    src = jnp.argmax(match, axis=-1)                           # (B, w)
    has = jnp.any(match, axis=-1)                              # (B, w)
    local_pos = jnp.where(has, jnp.take_along_axis(pos1d, src, axis=1),
                          -1).astype(jnp.int32)
    return src, has, local_pos


def _ring_from_prefill(k: jax.Array, src: jax.Array, has: jax.Array):
    """Gather (.., B, S, kv, hd) into ring layout (.., B, w, kv, hd)
    according to ``_ring_select``'s placement.  Leading stacked dims are
    preserved; empty slots are zeroed (masked by local_pos == −1)."""
    b, w = src.shape
    shape_idx = (1,) * (k.ndim - 4) + (b, w, 1, 1)
    idx = jnp.broadcast_to(src.reshape(shape_idx),
                           k.shape[:-3] + (w,) + k.shape[-2:])
    out = jnp.take_along_axis(k, idx, axis=-3)
    return jnp.where(jnp.broadcast_to(has.reshape(shape_idx), out.shape),
                     out, jnp.zeros((), out.dtype))


def _constrain_kv_cache(arr: jax.Array) -> jax.Array:
    """Stacked KV cache (..., B, S, kv, hd): store seq-sharded ("model"
    under prefill rules) — a replicated 32k cache costs model-axis ×
    the HBM (measured 21.5 GiB/device on qwen2-72b prefill)."""
    nd = arr.ndim
    axes = (None,) * (nd - 4) + ("act_batch", "act_cache_seq",
                                 "act_kv_heads", None)
    return constrain(arr, axes)


def _cache_from_prefill(cfg: ArchConfig, caches, positions, b, s,
                        policy: Optional[PrecisionPolicy] = None):
    caches = {k: (_constrain_kv_cache(v) if k.split("_")[-1] in ("k", "v")
                  else v)
              for k, v in caches.items()}
    cache: Dict[str, jax.Array] = {}
    pos1d = positions if positions.ndim == 2 else positions[..., 0]
    pat = layer_pattern(cfg)
    w = cfg.sliding_window

    if pat["kind"] in ("uniform_dense", "uniform_moe"):
        cache["k"], cache["v"] = caches["k"], caches["v"]
        cache["full_pos"] = pos1d
    elif pat["kind"] == "uniform_ssm":
        cache["ssm"] = caches["ssm"]
    elif pat["kind"] == "local_global":
        src, has, local_pos = _ring_select(pos1d, w)
        cache["local_k"] = _ring_from_prefill(caches["local_k"], src, has)
        cache["local_v"] = _ring_from_prefill(caches["local_v"], src, has)
        cache["global_k"], cache["global_v"] = (caches["global_k"],
                                                caches["global_v"])
        if "tail_k" in caches:
            cache["tail_k"] = _ring_from_prefill(caches["tail_k"], src, has)
            cache["tail_v"] = _ring_from_prefill(caches["tail_v"], src, has)
        cache["full_pos"] = pos1d
        cache["local_pos"] = local_pos
    elif pat["kind"] == "hybrid":
        cache["ssm"] = caches["ssm"]
        cache["attn_k"], cache["attn_v"] = caches["attn_k"], caches["attn_v"]
        cache["full_pos"] = pos1d
    if policy is not None and policy.kv_cache == "int8":
        # Quantize AFTER ring reconstruction (gather commutes with
        # per-entry quantization) so one code path covers every layout.
        cache = {key: (maybe_quant_kv(policy, arr)
                       if key.split("_")[-1] in ("k", "v") else arr)
                 for key, arr in cache.items()}
    return cache


def _grow_axis(arr: jax.Array, axis: int, extra: int) -> jax.Array:
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, extra)
    return jnp.pad(arr, pad)


def grow_cache(cfg: ArchConfig, cache, extra: int):
    """Extend full-attention cache seq dims by ``extra`` slots (padded)."""
    def grow(name, arr):
        if isinstance(arr, Int8KV):
            return Int8KV(_grow_axis(arr.q, -3, extra),
                          _grow_axis(arr.scale, -2, extra))
        return _grow_axis(arr, -3, extra)

    out = dict(cache)
    for key in ("k", "v", "global_k", "global_v", "attn_k", "attn_v"):
        if key in out:
            out[key] = grow(key, out[key])
    if "full_pos" in out:
        out["full_pos"] = jnp.pad(out["full_pos"], ((0, 0), (0, extra)),
                                  constant_values=-1)
    return out
