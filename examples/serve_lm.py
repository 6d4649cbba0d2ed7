"""Batched serving example: submit mixed-length prompts to the
continuous-batching server, ``PagedBatchServer`` (the EIM
process-runner analogue, paper §4.6), and report TTFT / throughput.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch zamba2-2.7b
"""
import argparse
import json

import jax
import numpy as np

from repro import configs
from repro.models.params import init_params
from repro.serve.server import PagedBatchServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    choices=list(configs.ALIASES))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--precision", choices=("float", "int8"),
                    default="float")
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch)   # reduced config on CPU
    params = init_params(cfg, jax.random.key(0))
    server = PagedBatchServer(
        cfg, params, slots=args.slots, max_prompt=args.prompt_len,
        max_new_tokens=args.max_new, precision=args.precision)
    rng = np.random.RandomState(0)
    # mixed-length workload: short and long prompts, varied budgets
    lens = [rng.randint(4, args.prompt_len + 1) for _ in range(args.requests)]
    budgets = [int(rng.randint(2, args.max_new + 1))
               for _ in range(args.requests)]
    reqs = server.submit(
        [rng.randint(0, cfg.vocab_size, n).astype(np.int32) for n in lens],
        max_new_tokens=budgets)
    metrics = server.run()
    print(json.dumps(metrics, indent=1))
    print("first request generated:", reqs[0].tokens)


if __name__ == "__main__":
    main()
