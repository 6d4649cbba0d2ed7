"""Readings that a cell's limit on ``max_logit_gap`` is set from, on the
chip, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 3] [--seconds 10] [--out chiprun_out/x.json]

For each seed it makes a benchmark run (``run.run``) at the cell's own
load and sizes, with a short window, and keeps what the run compared:
the program's reading, the lower one.  On the first ``--control-seeds``
seeds it makes the same run again with the control in the program's
place, one precision step below the configuration: for a float
configuration (bfloat16 compute), the program's own int8 path
(``precision="int8"``: int8 weights, activations and KV cache) serving
the same schedule from the same weights.  Its served tokens go through
the same comparison, against the same limit, so its run has to come out
``correct: false``.

Not part of any benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import run as R  # noqa: E402

LOWER = {"float": "int8"}       # the precision step below, per precision


def reading(bench, cell, config, traffic, seed, seconds, *,
            require_tpu=True) -> dict:
    """What one run compared, and whether it came out correct."""
    res = R.run(bench, cell, config, traffic, seed, seconds, False,
                require_tpu=require_tpu, t_start=time.perf_counter())
    if res is None:                     # no TPU: run.run said why
        raise SystemExit(1)
    gc.collect()
    info = res["info"]
    return {"correct": res["correct"],
            "max_logit_gap": res["compared"]["max_logit_gap"]["value"],
            "limit": res["compared"]["max_logit_gap"]["limit"],
            "mean_logit_gap": info["mean_logit_gap"],
            "miss_share": info["miss_share"],
            "sample_tokens": info["sample_tokens"]}


def one_seed(bench, cell, config, traffic, seed, seconds, control: bool,
             *, require_tpu=True) -> dict:
    row = {"seed": seed, "program": reading(
        bench, cell, config, traffic, seed, seconds,
        require_tpu=require_tpu)}
    if control:
        low = dict(config, precision=LOWER[config["precision"]])
        row["control"] = reading(bench, cell, low, traffic, seed, seconds,
                                 require_tpu=require_tpu)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    bench, cell, config, traffic = H.load_cell(a.workload)
    rows = []
    for n, seed in enumerate(int(s) for s in a.seeds.split(",")):
        row = one_seed(bench, cell, config, traffic, seed, a.seconds,
                       n < a.control_seeds)
        rows.append(row)
        print(json.dumps(row), flush=True)
        if a.out:
            Path(a.out).parent.mkdir(parents=True, exist_ok=True)
            Path(a.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
