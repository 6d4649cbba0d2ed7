"""Serving driver: ``python -m repro.launch.serve --arch <id>``.

Runs ``PagedBatchServer`` (block tables, prefix sharing,
preempt-and-recompute — docs/paged_kv.md) over synthetic prompts on the
selected arch: its smoke config by default, its published widths with
``--full`` (which needs the TPU: ``chip_smoke.py`` drives the engine
that way on one v5e chip).  Kernels run through Pallas on a TPU backend
and through the jnp references elsewhere.  ``--pool-blocks`` sizes the
pool below the ``slots × capacity`` rectangle, ``--artifact`` runs the
decode hot loop from an AOT ``CompiledArtifact`` (paper C4: serve the
deployed executable).
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import configs
from repro.launch import compile_cache
from repro.models.params import init_params
from repro.serve.server import PagedBatchServer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="physical KV blocks in the pool (default: the"
                         " slots × capacity rectangle's count)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="chunked pad-free admission: prompt tokens per"
                         " prefill chunk step (docs/scheduling.md)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--artifact", action="store_true",
                    help="decode via AOT CompiledArtifact (EON-style)")
    ap.add_argument("--precision", choices=("float", "int8"),
                    default="float",
                    help="int8: QTensor weights + dynamic activation quant"
                         " + Int8KV cache (paper C5 end-to-end)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.get(args.arch) if args.full else configs.get_smoke(args.arch)
    params = init_params(cfg, jax.random.key(0))
    server = PagedBatchServer(
        cfg, params, slots=args.slots, max_prompt=args.prompt_len,
        prefill_chunk=args.prefill_chunk,
        max_new_tokens=args.max_new, use_artifact=args.artifact,
        pool_blocks=args.pool_blocks, precision=args.precision)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=args.prompt_len)
               .astype(np.int32) for _ in range(args.requests)]
    server.submit(prompts)
    metrics = server.run()
    print(json.dumps(metrics, indent=1))


if __name__ == "__main__":
    main()
