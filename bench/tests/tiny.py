"""Tiny configurations, traffic and a cell for CPU tests of the harness:
the program's smoke widths (the dense one four layers deep, so that a
lost decode state shows in its logits), driven exactly as a chip run
drives a cell."""
from __future__ import annotations

import copy
import json

from bench.harness import BENCH, ROOT

DENSE = {"name": "internlm2-1.8b-smoke", "family": "dense", "n_layers": 4,
         "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
         "d_ff": 128, "vocab_size": 500, "rope_theta": 1000000.0,
         "norm_eps": 1e-05, "tie_embeddings": False, "dtype": "bfloat16",
         "vocab_pad_multiple": 64}

def config(name: str) -> dict:
    """The cell's configuration file with the model cut to smoke size
    and a small server."""
    base = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c = copy.deepcopy(base)
    c["model"] = dict(DENSE)
    c["server"] = {"slots": 4, "max_prompt": 64, "max_new_cap": 16,
                   "prefill_chunk": 32, "pool_blocks": 12}
    return c


def traffic() -> dict:
    return {"generator": "open_loop", "rate_rps": 4.0,
            "interarrival_cv": 1.0,
            "prompt": {"median": 24, "sigma": 0.8, "min": 4, "max": 64},
            "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
            "warmup_s": 1, "drain_limit_s": 20, "tail_max_new": 4}


def bench_and_cell(cell_name: str):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {c["name"]: c for c in bench["workloads"]}[cell_name]
    return bench, cell
