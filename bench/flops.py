"""Operations and bytes a kernel's work needs, from shapes alone, and
the roofline time they bound.  A multiply-add is two operations; the
model's operations per token are its reference module's
(``bench/models/<reference>.py``, ``token_flops``).
"""
from __future__ import annotations

from typing import Dict, Tuple

KV_BYTES = {"float": 2, "int8": 1}     # bfloat16 or int8 cache entries


def least_seconds(flops: Dict[str, float], nbytes: float,
                  peaks: dict) -> float:
    """The roofline: the larger of the compute time at peak (each
    precision at its own peak) and the bytes at peak bandwidth."""
    compute = (flops.get("bf16", 0.0) / peaks["bf16_flops_per_s"]
               + flops.get("int8", 0.0) / peaks["int8_ops_per_s"])
    return max(compute, nbytes / peaks["hbm_bytes_per_s"])


def decode_attention_call(m: dict, precision: str, slots: int,
                          read_rows: float, live_rows: float
                          ) -> Tuple[Dict[str, float], float]:
    """One call of the decode attention kernel (one layer, every slot):
    (operations, bytes).  ``read_rows`` is the KV rows the kernel has to
    read (whole pool blocks of every slot, idle slots one block), and
    ``live_rows`` the valid rows it attends over, both summed over the
    slots.  Bytes: K and V rows in the cache's precision, their int8
    scales, one int32 position per row, and the bfloat16 query and
    output rows."""
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    row = 2 * hkv * hd * KV_BYTES[precision] + 4
    if precision == "int8":
        row += 2 * hkv * 4
    nbytes = read_rows * row + 2 * slots * hq * hd * 2
    return {"bf16": 4.0 * hq * hd * live_rows}, float(nbytes)
