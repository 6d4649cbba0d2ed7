"""Find a cell's knee once: benchmark runs (``run.run``) of the cell at a
list of fixed rates, in one process.

    python3 bench/sweep.py --workload <name> --rates 1.5,2,2.5 \
        [--seconds 20] [--seed 7] [--out chiprun_out/sweep.json]

Each rate replaces the traffic file's; each run reports what its window
saw: requests due and finished, tokens per second, the median and 90th
percentile of the time to first token and of the time per output token,
the share of slots busy, and the requests still waiting at the window's
close (a backlog that grows with the window is past the knee).  The
traffic file's rate is what this sets; the sweep itself is not part of
any benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import run as R  # noqa: E402

KEEP = ("ttft_p50_ms", "tpot_p50_ms", "slot_utilization",
        "waiting_at_close", "finished")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    bench, cell, config, traffic = H.load_cell(a.workload)
    rows = []
    for rate in (float(r) for r in a.rates.split(",")):
        res = R.run(bench, cell, config, dict(traffic, rate_rps=rate),
                    a.seed, a.seconds, False, t_start=time.perf_counter())
        if res is None:
            return 1
        row = {"rate_rps": rate, "attempted": res["attempted"],
               **{k: v["value"] for k, v in res["metrics"].items()},
               **{k: res["info"].get(k) for k in KEEP}}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
