"""serve_step factories: prefill, paged chunked prefill, and one-token
decode, policy-wrapped.

Each factory takes an optional ``PrecisionPolicy`` (core/quantize):
the step closes over it, so float and int8 servers lower distinct
(but same-signature) executables.

``make_prefill_step`` and ``make_decode_step`` lower the one-shot
prefill and the unpaged one-token decode (``launch/dryrun.py``'s cells).
The serving engine runs the two paged steps.  Each step function's name
is its program's name once jitted (``jit_<name>`` in a profiler trace),
so the names are unique: ``jit_decode_step`` is only the paged decode
step and ``jit_paged_chunk_step`` only the paged chunk step, the two
programs the chip benchmark reads.  ``paged_chunk_step`` is the
admission path of chunked pad-free prefill: one fixed-size chunk of C
prompt tokens against one slot's view of the cache, compiled once per
chunk shape (instead of once per padded bucket).

With pad-free admission a slot's logical KV index always equals its
entry's absolute position, so the decode step derives its write index
from ``position`` and carries only the per-slot ``kv_len`` fill — the
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


def make_paged_chunk_prefill_step(cfg: ArchConfig, *, axes,
                                  rules: Optional[AxisRules] = None,
                                  mesh=None, policy=None):
    """Chunk-prefill step over the **paged** decode cache.

    ``axes`` comes from ``kvcache.paged_slot_axes``: slot-addressed
    leaves (ring caches, SSM state) are sliced out at the traced
    ``slot`` index, run at batch 1 and spliced back, while the paged
    pool leaves pass through whole — the chunk addresses them via
    ``block_row``, the (1, n_blocks) block-table row of the slot being
    prefilled: ``step(params, cache, tokens, positions, slot, kv_len,
    block_row) -> (next_tokens (1, C), logits, new_cache)``.

    ``tokens``/``positions``: (1, C) with the pad tail of a ragged final
    chunk at position −1; ``kv_len``: (1,) the *logical* post-write fill
    ``p + C``.  The chunk's write offset is ``positions[:, 0]`` (the
    first entry of a chunk is always a real token).
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


def make_paged_decode_step(cfg: ArchConfig, *,
                           rules: Optional[AxisRules] = None, mesh=None,
                           policy=None):
    """Decode step over the paged decode cache (paged continuous
    batching): ``step(params, cache, token, position, kv_len,
    block_table)``.

    ``policy`` (``PrecisionPolicy``) selects the weight/activation/KV
    precision the step lowers with — it is part of the compiled
    artifact's identity, not a runtime argument.

    ``kv_len`` (B,) is the scheduler's exact per-slot fill: with pad-free
    chunked admission a slot's logical KV index equals its entry's
    absolute position, so the write index is simply ``position`` and the
    post-write fill is ``position + 1``.  ``kv_len == 0`` marks a slot
    that is idle or mid-prefill: reads skip it and its writes are routed
    out of bounds, so decode steps can interleave with chunked prefill
    on the same cache.  ``block_table`` (slots, n_blocks) resolves each
    slot's logical KV blocks to physical pool blocks (docs/paged_kv.md).
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
