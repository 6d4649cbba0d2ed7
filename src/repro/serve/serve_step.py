"""serve_step factories: prefill, chunked prefill, and one-token decode,
policy-wrapped.

Each factory takes an optional ``PrecisionPolicy`` (core/quantize):
the step closes over it, so float and int8 servers lower distinct
(but same-signature) executables.

``decode_*`` shapes lower ``plain_decode_step`` (one new token against a
KV cache of seq_len), ``prefill_*`` shapes lower ``prefill_step`` — per
the assignment's cell semantics.  Each step function's name is its
program's name once jitted (``jit_<name>`` in a profiler trace), so the
names are unique: ``jit_decode_step`` is only the paged decode step and
``jit_paged_chunk_step`` only the paged chunk step, the two programs
the chip benchmark reads.  ``chunk_prefill_step`` is the admission
path of chunked pad-free prefill: one fixed-size chunk of C prompt
tokens against one slot's live cache row, compiled once per chunk shape
(instead of once per padded bucket).

With pad-free admission a cache row's index always equals its entry's
absolute position, so the slot decode step derives its write index from
``position`` and carries only the per-slot ``kv_len`` fill — the
scheduler's exact live length, no pad region (see docs/scheduling.md
for the invariants).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.arch import ArchConfig
from repro.models.api import model_fns
from repro.serve.kvcache import put_slot, take_slot
from repro.sharding.policy import AxisRules, use_rules


def _context(fn, rules, mesh):
    if rules is None or mesh is None:
        return fn

    @functools.wraps(fn)
    def wrapped(*a, **k):
        with use_rules(rules, mesh):
            return fn(*a, **k)
    return wrapped


def make_prefill_step(cfg: ArchConfig, *, rules: Optional[AxisRules] = None,
                      mesh=None, policy=None):
    fns = model_fns(cfg)

    def prefill_step(params, inputs):
        logits, cache = fns.forward_prefill(cfg, params, inputs, policy)
        # greedy next token (sampling lives host-side in the server loop)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, cache

    return _context(prefill_step, rules, mesh)


def make_chunk_prefill_step(cfg: ArchConfig, *, axes=None,
                            rules: Optional[AxisRules] = None, mesh=None,
                            policy=None):
    """Chunked pad-free prefill step (the serving admission path).

    Without ``axes``: ``step(params, cache, tokens, positions, kv_len)``
    runs one (B, C) chunk against a batch-matched cache — the model-
    level building block.

    With ``axes`` (a ``kvcache.slot_batch_axes`` pytree): the step takes
    the *big* slots × capacity cache plus a traced ``slot`` index,
    slices that slot's row out, runs the chunk at batch 1, and splices
    the row back — so a prefill chunk costs one slot's attention, not
    the whole batch's: ``step(params, cache, tokens, positions, slot,
    kv_len) -> (next_tokens (1, C), logits, new_cache)``.

    ``tokens``/``positions``: (B, C) with the pad tail of a ragged final
    chunk at position −1; ``kv_len``: (B,) post-write fill ``p + C``.
    The chunk's write offset is ``positions[:, 0]`` (the first entry of
    a chunk is always a real token).
    """
    fns = model_fns(cfg)

    def chunk_step(params, cache, tokens, positions, kv_len):
        logits, new_cache = fns.forward_prefill_chunk(
            cfg, params, cache, tokens, positions, policy=policy,
            kv_len=kv_len)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, logits, new_cache

    if axes is None:
        return _context(chunk_step, rules, mesh)

    def slot_chunk_step(params, cache, tokens, positions, slot, kv_len):
        small = take_slot(cache, axes, slot)
        next_tokens, logits, new_small = chunk_step(params, small, tokens,
                                                    positions, kv_len)
        return next_tokens, logits, put_slot(cache, new_small, axes, slot)

    return _context(slot_chunk_step, rules, mesh)


def make_paged_chunk_prefill_step(cfg: ArchConfig, *, axes,
                                  rules: Optional[AxisRules] = None,
                                  mesh=None, policy=None):
    """Chunk-prefill step over the **paged** decode cache.

    ``axes`` comes from ``kvcache.paged_slot_axes``: slot-addressed
    leaves (ring caches, SSM state) are sliced/spliced per slot exactly
    as in ``make_chunk_prefill_step``, while the paged pool leaves pass
    through whole — the chunk addresses them via ``block_row``, the
    (1, n_blocks) block-table row of the slot being prefilled:
    ``step(params, cache, tokens, positions, slot, kv_len, block_row)``.
    ``kv_len`` stays the *logical* post-write fill ``p + C``.
    """
    fns = model_fns(cfg)

    def paged_chunk_step(params, cache, tokens, positions, slot, kv_len,
                         block_row):
        small = take_slot(cache, axes, slot)
        logits, new_small = fns.forward_prefill_chunk(
            cfg, params, small, tokens, positions, policy=policy,
            kv_len=kv_len, block_table=block_row)
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, logits, put_slot(cache, new_small, axes, slot)

    return _context(paged_chunk_step, rules, mesh)


def make_decode_step(cfg: ArchConfig, *, rules: Optional[AxisRules] = None,
                     mesh=None, policy=None):
    fns = model_fns(cfg)

    def plain_decode_step(params, cache, token, position):
        logits, new_cache = fns.forward_decode(cfg, params, cache, token,
                                               position, policy=policy)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    return _context(plain_decode_step, rules, mesh)


def make_slot_decode_step(cfg: ArchConfig, *,
                          rules: Optional[AxisRules] = None, mesh=None,
                          policy=None):
    """Decode step over the slot-addressed cache (continuous batching).

    ``policy`` (``PrecisionPolicy``) selects the weight/activation/KV
    precision the step lowers with — it is part of the compiled
    artifact's identity, not a runtime argument.

    ``kv_len`` (B,) is the scheduler's exact per-slot fill: with pad-free
    chunked admission a cache row's index equals its entry's absolute
    position, so the write index is simply ``position`` and the
    post-write fill is ``position + 1``.  ``kv_len == 0`` marks a slot
    that is idle or mid-prefill: the decode attention skips its row
    outright AND every cache/state write for it is suppressed, so decode
    steps can interleave with chunked prefill on the same cache.  The
    caller owns the contract that entries at index >= kv_len are invalid
    — which pad-free admission guarantees (chunks write ``[p, p + C)``
    exactly; decode writes advance the fill by one).
    """
    fns = model_fns(cfg)

    def slot_decode_step(params, cache, token, position, kv_len):
        logits, new_cache = fns.forward_decode(cfg, params, cache, token,
                                               position, policy=policy,
                                               kv_len=kv_len)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    return _context(slot_decode_step, rules, mesh)


def make_paged_decode_step(cfg: ArchConfig, *,
                           rules: Optional[AxisRules] = None, mesh=None,
                           policy=None):
    """Decode step over the paged decode cache (paged continuous
    batching): ``step(params, cache, token, position, kv_len,
    block_table)`` — the slot decode contract of
    ``make_slot_decode_step`` plus the (slots, n_blocks) block table
    that resolves each slot's logical KV blocks to physical pool blocks
    (docs/paged_kv.md).  ``kv_len == 0`` still marks idle/mid-prefill
    rows: reads skip them and their writes are routed out of bounds.
    """
    fns = model_fns(cfg)

    def decode_step(params, cache, token, position, kv_len, block_table):
        logits, new_cache = fns.forward_decode(cfg, params, cache, token,
                                               position, policy=policy,
                                               kv_len=kv_len,
                                               block_table=block_table)
        next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_token, logits, new_cache

    return _context(decode_step, rules, mesh)
