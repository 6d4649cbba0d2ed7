"""The weights' at-rest form a server holds (``quantize.serving_params``).

Every use of a projection or embedding table rounds it to the activation
dtype first, so a server that holds those leaves in the activation dtype
from the start must serve the same bits as one holding the float32
masters.  The steps run at ``dtype="bfloat16"``, where the conversion is
real (at float32 it is a no-op), against the tree a server held before:
the float32 masters, with ``QTensor`` projections at int8.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.quantize import (QUANT_SCOPES, TABLES, QTensor, policy_for,
                                 quantize_model_params, serving_params)
from repro.models.params import init_params
from repro.serve.kvcache import alloc_paged_cache, paged_slot_axes
from repro.serve.serve_step import (make_paged_chunk_prefill_step,
                                    make_paged_decode_step)
from repro.serve.server import PagedBatchServer

SLOTS, CAPACITY, BLOCK, CHUNK = 2, 32, 8, 8
ARCHS = ["internlm2-1.8b", "gemma3-4b", "falcon-mamba-7b", "zamba2-2.7b"]


@functools.lru_cache(maxsize=None)
def _setup(arch):
    """The smoke config at bfloat16, its leaves initialised to zero
    (norms, biases) given N(0, 0.1^2) values so that rounding them would
    show."""
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype="bfloat16")
    params = init_params(cfg, jax.random.key(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    leaves = [0.1 * jax.random.normal(k, x.shape, x.dtype)
              if not jnp.any(x) else x for k, x in zip(keys, leaves)]
    return cfg, jax.tree.unflatten(tree, leaves)


def _nbytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def _paged_steps(cfg, params, prec):
    """One chunk step (slot 0, a ragged chunk) and one decode step (slot
    0 live, slot 1 idle) on a scrambled block table: their logits and
    the cache after each."""
    n_table = CAPACITY // BLOCK
    pool = SLOTS * n_table
    table = np.random.RandomState(1).permutation(pool).astype(
        np.int32).reshape(SLOTS, n_table)
    axes = paged_slot_axes(cfg, SLOTS, CAPACITY, pool, prec, BLOCK)
    cache = alloc_paged_cache(cfg, SLOTS, CAPACITY, pool, prec, BLOCK)
    rows = CHUNK - 3
    toks = np.zeros((1, CHUNK), np.int32)
    poss = np.full((1, CHUNK), -1, np.int32)
    toks[0, :rows] = np.random.RandomState(2).randint(0, cfg.vocab_size,
                                                       rows)
    poss[0, :rows] = np.arange(rows)
    chunk = jax.jit(make_paged_chunk_prefill_step(cfg, axes=axes,
                                                  policy=prec))
    ntok, chunk_logits, cache = chunk(
        params, cache, jnp.asarray(toks), jnp.asarray(poss), 0,
        jnp.asarray([CHUNK], jnp.int32), jnp.asarray(table[:1]))
    chunk_cache = jax.tree.map(np.asarray, cache)
    tok = np.asarray([int(np.asarray(ntok)[0, rows - 1]), 0], np.int32)
    pos = np.asarray([rows, 0], np.int32)
    kvl = np.asarray([rows + 1, 0], np.int32)
    decode = jax.jit(make_paged_decode_step(cfg, policy=prec))
    _, decode_logits, cache = decode(params, cache, tok, pos, kvl,
                                     jnp.asarray(table))
    return chunk_logits, chunk_cache, decode_logits, cache


@pytest.mark.parametrize("precision", ["float", "int8"])
@pytest.mark.parametrize("arch", ARCHS)
def test_serving_params_bit_identical(arch, precision):
    cfg, raw = _setup(arch)
    prec = policy_for(precision)
    held = serving_params(raw, prec, cfg.activation_dtype)
    before = quantize_model_params(raw, prec)
    # the conversion is real here: the tables change dtype
    assert held["embed"].dtype == jnp.bfloat16
    assert raw["embed"].dtype == jnp.float32
    got = _paged_steps(cfg, held, prec)
    want = _paged_steps(cfg, before, prec)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert jnp.array_equal(g, w), (arch, precision)


def _path_keys(path):
    return [getattr(k, "key", None) for k in path]


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "zamba2-2.7b"])
def test_server_holds_at_rest_weights(arch):
    """``weight_bytes`` counts the tree the steps read; projections and
    tables are held in the activation dtype, every other leaf (norms,
    SSM dynamics and projections) keeps float32, and the caller's tree
    is not touched."""
    cfg, raw = _setup(arch)
    srv = PagedBatchServer(cfg, raw, slots=1, max_prompt=8,
                           prefill_chunk=8, max_new_tokens=2)
    srv.submit([np.arange(1, 6, dtype=np.int32)])
    m = srv.run()
    assert m["weight_bytes"] == _nbytes(srv.params)
    held = jax.tree_util.tree_leaves_with_path(srv.params)
    assert len(held) == len(jax.tree.leaves(raw))
    n_converted = 0
    for path, leaf in held:
        keys = _path_keys(path)
        converted = (keys[0] in TABLES
                     or (leaf.ndim >= 2
                         and any(k in QUANT_SCOPES for k in keys)))
        assert leaf.dtype == (jnp.bfloat16 if converted
                              else jnp.float32), keys
        n_converted += converted
    assert n_converted > len(TABLES)
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(raw))
    if arch == "internlm2-1.8b":
        assert srv.params["blocks"]["attn_norm"].dtype == jnp.float32
        assert m["weight_bytes"] <= 0.51 * _nbytes(raw)
    else:
        mamba = srv.params["groups"]["mamba"]
        for name in ("a_log", "dt_bias", "d_skip", "conv_w", "in_proj"):
            assert mamba[name].dtype == jnp.float32, name


def test_serving_params_is_idempotent_and_noop_at_float32():
    """Converting held weights again changes nothing, and at a float32
    activation dtype the float tree is the caller's own leaves."""
    cfg, raw = _setup("internlm2-1.8b")
    for precision in ("float", "int8"):
        prec = policy_for(precision)
        held = serving_params(raw, prec, cfg.activation_dtype)
        again = serving_params(held, prec, cfg.activation_dtype)
        for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(again)):
            assert a is b
    same = serving_params(raw, policy_for("float"), jnp.float32)
    for a, b in zip(jax.tree.leaves(raw), jax.tree.leaves(same)):
        assert a is b
    q = serving_params(raw, policy_for("int8"), jnp.float32)
    assert isinstance(q["blocks"]["mlp"]["w_up"], QTensor)
