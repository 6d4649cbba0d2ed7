"""Pieces the plain references share: float32 matmuls at the highest
precision, RMSNorm, RoPE, causal attention, SwiGLU, and the reduction
of a sequence's final hidden states to the numbers the check compares."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LOGIT_BLOCK = 256


def mm(x, w):
    """x @ w in float32 at the highest precision."""
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(w, x, eps):
    """Weights are stored as the offset from 1."""
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w)


def rope(x, pos, theta):
    """Rotate-half RoPE; x (B, L, H, D), pos (B, L)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None, None].astype(jnp.float32) * inv       # (B,L,1,D/2)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def attention(p, x, m):
    """Causal GQA self-attention over the whole sequence; x (B, L, d)."""
    b, n, _ = x.shape
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.broadcast_to(jnp.arange(n), (b, n))
    q = rope(mm(x, p["wq"]).reshape(b, n, hq, hd), pos, m["rope_theta"])
    k = rope(mm(x, p["wk"]).reshape(b, n, hkv, hd), pos, m["rope_theta"])
    v = mm(x, p["wv"]).reshape(b, n, hkv, hd)
    q = q.reshape(b, n, hkv, hq // hkv, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=HIGHEST)
    s = s * hd ** -0.5
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    return mm(o.reshape(b, n, hq * hd), p["wo"])


def swiglu(p, x):
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]),
              p["w_down"])


def dense_block(p, x, m):
    x = x + attention(p["attn"], rms_norm(p["attn_norm"], x, m["norm_eps"]),
                      m)
    return x + swiglu(p["mlp"], rms_norm(p["mlp_norm"], x, m["norm_eps"]))


def logit_stats(h, table, targets, vocab):
    """Per position of ``h`` (B, L, d): the largest logit over the real
    vocabulary, the logit of ``targets`` (B, L) (−inf for an id outside
    it) and their standard deviation over the vocabulary.  Logits are
    made ``LOGIT_BLOCK`` positions at a time so that no (B, L, vocab)
    array is ever live."""
    table = table[:vocab]
    b, n, d = h.shape
    nb = n // LOGIT_BLOCK

    def block(args):
        hb, tb = args                                          # (B, blk, ·)
        logits = jnp.einsum("bld,vd->blv", hb, table, precision=HIGHEST)
        inside = tb < vocab
        picked = jnp.take_along_axis(
            logits, jnp.where(inside, tb, 0)[..., None], axis=-1)[..., 0]
        return (logits.max(-1), jnp.where(inside, picked, -jnp.inf),
                logits.std(-1))

    hs = h.reshape(b, nb, LOGIT_BLOCK, d).swapaxes(0, 1)
    ts = targets.reshape(b, nb, LOGIT_BLOCK).swapaxes(0, 1)
    out = lax.map(block, (hs, ts))
    return tuple(o.swapaxes(0, 1).reshape(b, n) for o in out)
