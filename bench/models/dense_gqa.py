"""Plain float32 reference of a uniform dense decoder with grouped-query
attention (internlm2): pre-norm RMSNorm, RoPE, causal softmax attention,
SwiGLU MLP, untied output table.  Imports nothing of the program."""
from __future__ import annotations

from typing import Dict

from jax import lax

from bench.models.common import dense_block, rms_norm


def _block_matmuls(m: dict) -> Dict[str, tuple]:
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    nq, nkv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return {"attn": {"wq": (d, nq), "wk": (d, nkv), "wv": (d, nkv),
                     "wo": (nq, d)},
            "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}}


def weight_spec(m: dict, vocab_rows: int) -> dict:
    """{name: (kind, shape)} nested like the program's parameters, the
    layers stacked on the leading axis."""
    d, n = m["d_model"], m["n_layers"]
    block = {"attn_norm": ("norm", (n, d)), "mlp_norm": ("norm", (n, d))}
    for scope, mats in _block_matmuls(m).items():
        block[scope] = {k: ("matrix", (n, *s)) for k, s in mats.items()}
    tree = {"embed": ("embed", (vocab_rows, d)),
            "final_norm": ("norm", (d,)), "blocks": block}
    if not m["tie_embeddings"]:
        tree["unembed"] = ("embed", (vocab_rows, d))
    return tree


def token_flops(m: dict, precision: str, context: float
                ) -> Dict[str, float]:
    """Model operations for one token that attends over ``context``
    earlier positions, by the precision they run in: the layers'
    projections in int8 on the int8 path and in bfloat16 otherwise; the
    attention itself and the output head (real vocabulary) in bfloat16.
    A multiply-add is two operations."""
    per_layer = sum(k * n for mats in _block_matmuls(m).values()
                    for k, n in mats.values())
    out = {"bf16": 0.0, "int8": 0.0}
    out["int8" if precision == "int8" else "bf16"] += \
        2.0 * per_layer * m["n_layers"]
    out["bf16"] += 4.0 * m["n_heads"] * m["head_dim"] * context \
        * m["n_layers"]
    out["bf16"] += 2.0 * m["vocab_size"] * m["d_model"]
    return out


def forward(m: dict, w: dict, tokens):
    """Final hidden states (B, L, d) of ``tokens`` (B, L)."""
    x = w["embed"][tokens]

    def layer(x, p):
        return dense_block(p, x, m), None

    x, _ = lax.scan(layer, x, w["blocks"])
    return rms_norm(w["final_norm"], x, m["norm_eps"])


def output_table(m: dict, w: dict):
    return w["embed"] if m["tie_embeddings"] else w["unembed"]
