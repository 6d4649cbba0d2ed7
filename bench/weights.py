"""Seeded random weights, made on the device in one jitted call.

The layout comes from the configuration's reference module
(``bench/models/<reference>.py``, its ``weight_spec``), which follows
the program's parameter tree, so the reference and the server read the
same arrays and neither depends on the program's own initializer.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

from bench.models import reference

MATRIX_STD = 0.02
NORM_STD = 0.1


def padded_vocab(m: dict) -> int:
    mult = m["vocab_pad_multiple"]
    return int(math.ceil(m["vocab_size"] / mult) * mult)


def _leaf(kind: str, shape, key, vocab: int):
    if kind == "matrix":
        return jax.random.normal(key, shape, jnp.float32) * MATRIX_STD
    if kind == "embed":
        w = jax.random.normal(key, shape, jnp.float32) * MATRIX_STD
        rows = jnp.arange(shape[0])[:, None]
        return jnp.where(rows < vocab, w, 0.0)
    if kind == "norm":
        return jax.random.normal(key, shape, jnp.float32) * NORM_STD
    raise ValueError(f"no weights of kind {kind!r}")


def _path_key(key, path) -> jax.Array:
    name = "/".join(str(getattr(p, "key", p)) for p in path)
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def spec_tree(config: dict) -> dict:
    m = config["model"]
    return reference(config).weight_spec(m, padded_vocab(m))


def builder(config: dict):
    """The function of a PRNG key that makes every weight of ``config``."""
    vocab = config["model"]["vocab_size"]
    spec = spec_tree(config)

    def build(key):
        return jax.tree_util.tree_map_with_path(
            lambda path, s: _leaf(s[0], s[1], _path_key(key, path), vocab),
            spec, is_leaf=lambda s: isinstance(s, tuple))
    return build


def make(config: dict, seed: int):
    """All weights of ``config`` from ``seed``, on the default device, in
    one jitted call."""
    out = jax.jit(builder(config))(jax.random.key(seed))
    jax.block_until_ready(out)
    return out
