"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``, read by the generator it names); each
per-layer metric is read by ``bench/metrics/<metric>.py``.  The run:

1. set-up: seeded weights made on the device in one jitted call, a
   ``PagedBatchServer`` at the configuration's settings, and one small
   batch through it so that the chunk and decode programs are compiled
   (or loaded from JAX's persistent cache) before any request is timed;
2. an open loop: warm-up, the measured window of ``--seconds``, and a
   tail that keeps arrivals on until every request due in the window
   has finished or the traffic file's drain limit has passed, after
   which the server serves what it holds;
3. with ``--trace 1``, a profiler trace of a few seconds in the middle
   of the window, reduced to the cell's per-layer metrics;
4. the check: a sample of the window's requests through the plain
   float32 reference (``bench/correct.py``), once the server is freed.

The last line of standard output is one JSON object; the numbers that
decide ``correct`` are printed beside their limits as the last lines of
standard error and under ``compared``, the result's last key.  A run
that finds no TPU, or fewer chips than the cell asks for, exits 1 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402


def peaks_for(kind: str) -> dict:
    table = json.loads((H.BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][kind]


def run(bench: dict, cell: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace: bool, *, require_tpu: bool = True,
        t_start: float = T_START) -> Optional[dict]:
    """One run of ``cell``: the result object, or None where JAX finds
    no TPU or fewer chips than the cell asks for."""
    import jax
    from repro.launch import compile_cache
    from bench import correct, weights
    compile_cache.enable()
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < cell["chips"]):
        H.log(f"no TPU for {cell['name']}: JAX sees {len(devs)} "
              f"{devs[0].platform} device(s), the cell asks for "
              f"{cell['chips']} TPU chip(s); no result")
        return None
    dev = devs[0]
    counter = H.CompileCounter()
    marks = {"devices": time.perf_counter()}

    # ---- set-up ------------------------------------------------------
    cfg = H.arch_config(config)
    w = weights.make(config, seed)
    H.check_layout(cfg, w)
    marks["weights"] = time.perf_counter()
    srv = H.make_server(config, cfg, w)
    H.warm(srv, config, cfg.vocab_size)
    marks["warm"] = time.perf_counter()
    items = H.schedule(traffic, seed, seconds, cfg.vocab_size)

    def arm():
        counter.count = 0
        counter.armed = True

    tracer = None
    if trace:
        shutil.rmtree(H.TRACE_DIR, ignore_errors=True)
        tracer = Tracer()
    recs, times, stats = H.drive(
        srv, items, seconds, traffic["drain_limit_s"], on_window_start=arm,
        trace_at=(tracer.start, tracer.stop) if tracer else None)
    counter.armed = False
    e2e = H.end_to_end(items, recs, times)
    setup_s = times["window_start"] - t_start
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    capacity = srv.capacity
    plens = [len(r[0].prompt) for r in recs if r is not None]
    window = [recs[i] for i, it in enumerate(items) if it.phase == "window"]
    finished = [(r.prompt, list(r.tokens)) for r, _, _ in
                (x for x in window if x is not None) if r.done]
    short = sum(1 for r, _, _ in (x for x in window if x is not None)
                if r.done and len(r.tokens) != r.max_new_tokens)
    failed = e2e["attempted"] - e2e["finished"] + short
    del srv, recs, window
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": int(mem)}
    metrics, breakdown = {}, None
    if trace:
        from bench import trace as T
        chips = T.load(H.TRACE_DIR)
        chip = chips[sorted(chips)[0]] if chips else None
        busy = [T.busy_ns(c) * 1e-9 for c in chips.values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = tracer.window_s
        ctx = {"config": config, "cell": cell, "stats": stats.means(),
               "chip": chip, "busy_s": device["busy_s"],
               "window_s": tracer.window_s,
               "peaks": peaks_for(dev.device_kind),
               "capacity": capacity,
               "prompt_tokens": sum(plens),
               "prompt_positions": sum(n * (n - 1) / 2 for n in plens)}
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            v = H.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if chip is not None:
            breakdown = {"device_ops": T.top_ops(chip),
                         "idle_gaps": T.idle_gaps(chip)}
    else:
        for m in bench["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    marks["reduced"] = time.perf_counter()

    # ---- the check ---------------------------------------------------
    limit = config["correct"]["max_logit_gap"]
    pick = correct.pick_sample(finished, config["correct"]["sample_requests"],
                               seed)
    sample = [finished[i] for i in pick]
    read = correct.summarize(
        correct.served_gaps(config, w, sample) if sample else [])
    marks["check"] = time.perf_counter()
    gap = read["max_gap"]
    compared = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": counter.count, "limit": 0},
    }
    ok = gap <= limit and failed == 0 and counter.count == 0
    info = {k: e2e[k] for k in ("ttft_p50_ms", "tpot_p50_ms",
                                "feeder_late_p50_ms", "feeder_late_max_ms",
                                "finished", "tokens") if k in e2e}
    info.update(stats.means())
    info.update({"waiting_at_close": times.get("waiting_at_close"),
                 "sample_requests": len(pick),
                 "sample_tokens": read["tokens"],
                 "mean_logit_gap": read["mean_gap"],
                 "miss_share": read["miss_share"]})
    # where a run's time goes, in seconds (setup_s is the metric)
    info["phases_s"] = {
        "start_to_devices": marks["devices"] - t_start,
        "weights": marks["weights"] - marks["devices"],
        "server_and_warm": marks["warm"] - marks["weights"],
        "warmup_traffic": times["window_start"] - marks["warm"],
        "window": times["window_end"] - times["window_start"],
        "drain": times["arrivals_end"] - times["window_end"],
        "tail": times["served_end"] - times["arrivals_end"],
        "reduce": marks["reduced"] - times["served_end"],
        "check": marks["check"] - marks["reduced"],
        "total": marks["check"] - t_start}
    result = {"correct": bool(ok), "attempted": e2e["attempted"],
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = info
    result["compared"] = compared
    H.log("phases (s): " + json.dumps(info["phases_s"]))
    for k, v in compared.items():
        H.log(f"compared {k}: {v['value']} (limit {v['limit']})")
    return result


class Tracer:
    """Starts and stops the profiler from timer threads; records how
    long it ran."""

    def __init__(self):
        self.window_s = 0.0
        self._t = 0.0

    def start(self):
        import jax
        jax.profiler.start_trace(str(H.TRACE_DIR))
        self._t = time.perf_counter()

    def stop(self):
        import jax
        self.window_s = time.perf_counter() - self._t
        jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    bench, cell, config, traffic = H.load_cell(a.workload)
    result = run(bench, cell, config, traffic, a.seed, a.seconds,
                 bool(a.trace))
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
