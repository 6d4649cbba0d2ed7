"""One benchmark run of one cell: set-up, an open-loop window, the
per-layer reduction and the check of what the window served.

The system under test is ``PagedBatchServer``, driven only through its
public ``submit`` and ``run``: a feeder thread hands each request to
``submit`` when it is due, and the main thread calls ``run`` whenever
work is waiting.  Every time is taken from the request's due time.
"""
from __future__ import annotations

import importlib
import json
import sys
import threading
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 4.0
FEEDER_POLL_S = 0.1     # how often the feeder looks for the end, at most


def load_cell(name: str, root: Path = ROOT):
    """(benchmark, cell, config, traffic) dicts for workload ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def arch_config(config: dict):
    from repro.core.arch import ArchConfig
    return ArchConfig(**config["model"])


def check_layout(cfg, params) -> None:
    """The bench's tree has the program's structure and shapes."""
    import jax
    from repro.models.params import abstract_params
    want = abstract_params(cfg)
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise SystemExit("weight tree does not match the program's layout")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        if a.shape != b.shape:
            raise SystemExit(f"weight shape {b.shape} != program's {a.shape}")


def make_server(config: dict, cfg, params):
    from repro.serve.server import PagedBatchServer
    s = config["server"]
    return PagedBatchServer(
        cfg, params, slots=s["slots"], max_prompt=s["max_prompt"],
        prefill_chunk=s["prefill_chunk"], max_new_tokens=s["max_new_cap"],
        max_new_cap=s["max_new_cap"], pool_blocks=s["pool_blocks"],
        precision=config["precision"])


def warm(srv, config: dict, vocab: int) -> None:
    """Compile, or load from the persistent cache, every program the
    window drives, at the cell's shapes: the chunk step (a prompt of a
    chunk and one more token) and the decode step."""
    s = config["server"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, n, dtype=np.int32)
               for n in (s["prefill_chunk"] + 1, 3)]
    srv.submit(prompts, [2, 2])
    srv.run()


class RunStats:
    """Server counters summed over every ``run()`` call, weighted by
    decode steps where the server reports a per-step mean."""
    MEANS = ("slot_utilization", "kv_read_frac", "kv_fill_frac")

    def __init__(self):
        self.steps = 0
        self.chunks = 0
        self.sums = {k: 0.0 for k in self.MEANS}

    def add(self, m: dict) -> None:
        n = int(m.get("decode_steps", 0))
        self.steps += n
        self.chunks += int(m.get("prefill_chunks", 0))
        for k in self.MEANS:
            if k in m and n:
                self.sums[k] += m[k] * n

    def means(self) -> dict:
        out = {"decode_steps": self.steps, "prefill_chunks": self.chunks}
        if self.steps:
            out.update({k: v / self.steps for k, v in self.sums.items()})
        return out


def drive(srv, items, seconds: float, drain_limit: float,
          on_window_start=None, trace_at=None):
    """Serve ``items`` open loop.  Returns (records, times, stats)
    where records[i] = (request, due, lateness) for every submitted
    item, in schedule order.  Arrivals stop once every request due in
    the window has finished, or ``drain_limit`` seconds after the
    window's close; ``run`` then serves what was submitted."""
    warm_s = next(it.due for it in items if it.phase == "window")
    recs: List[Optional[tuple]] = [None] * len(items)
    wake, stop = threading.Event(), threading.Event()
    t0 = time.perf_counter() + 0.05
    times = {"t0": t0, "window_start": t0 + warm_s,
             "window_end": t0 + warm_s + seconds}
    deadline = times["window_end"] + drain_limit
    window = [i for i, it in enumerate(items) if it.phase == "window"]

    def ended() -> bool:
        now = time.perf_counter()
        if now <= times["window_end"]:
            return False
        return now > deadline or all(
            recs[i] is not None and recs[i][0].done for i in window)

    def feeder():
        try:
            for i, it in enumerate(items):
                due = t0 + it.due
                while (left := due - time.perf_counter()) > 0:
                    if ended():
                        return
                    time.sleep(min(left, FEEDER_POLL_S))
                if ended():
                    return
                req = srv.submit([it.prompt], [it.max_new])[0]
                recs[i] = (req, due, time.perf_counter() - due)
                wake.set()
        finally:
            times["arrivals_end"] = time.perf_counter()
            stop.set()
            wake.set()

    def at_close():
        times["waiting_at_close"] = len(srv.sched.waiting)

    thread = threading.Thread(target=feeder, daemon=True)
    stats = RunStats()
    hooks = [threading.Timer(max(times["window_end"] - time.perf_counter(),
                                 0), at_close)]
    if on_window_start is not None:
        hooks.append(threading.Timer(max(times["window_start"]
                                         - time.perf_counter(), 0),
                                     on_window_start))
    if trace_at is not None:
        hooks.append(threading.Thread(
            target=_trace, args=(trace_at, recs, stop,
                                 times["window_start"]
                                 + (seconds - TRACE_SECONDS) / 2)))
    thread.start()
    for h in hooks:
        h.start()
    while True:
        wake.clear()
        fed = stop.is_set()          # read first: no submit follows it
        if srv.sched.busy:
            stats.add(srv.run())
            continue
        if fed:
            break
        wake.wait(0.01)
    times["served_end"] = time.perf_counter()
    thread.join()
    for h in hooks:
        h.join()
    return recs, times, stats


def _trace(trace_at, recs, stop, at: float) -> None:
    """Profile from ``at`` for ``TRACE_SECONDS``, and on until a request
    that arrived after the start has its first token, so that the trace
    holds a chunk-prefill step even in a gap between bursts (or until the
    feeder stops)."""
    start, end = trace_at
    stop.wait(max(at - time.perf_counter(), 0))
    start()
    began = time.perf_counter()
    while not stop.is_set():
        if time.perf_counter() - began >= TRACE_SECONDS and any(
                r is not None and r[1] >= began
                and r[0].first_token_at is not None for r in list(recs)):
            break
        time.sleep(0.05)
    end()


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def tokens_in(req, start: float, end: float) -> int:
    """Output tokens of ``req`` emitted in [start, end).  The server
    stamps only the first token and the finish, so the tokens between
    are taken as evenly spaced (one per decode step of the slot)."""
    n = len(req.tokens)
    if not n or req.first_token_at is None:
        return 0
    last = req.finished_at or req.first_token_at
    t = req.first_token_at + (np.arange(n) * ((last - req.first_token_at)
                                             / max(n - 1, 1)))
    return int(np.count_nonzero((t >= start) & (t < end)))


def end_to_end(items, recs, times) -> dict:
    """Window metrics: the tails over every request due in the window;
    the rate over every output token emitted in the window."""
    window = [recs[i] for i, it in enumerate(items) if it.phase == "window"]
    done = [r for r in window if r is not None and r[0].done]
    ttft = [(r.first_token_at - due) * 1e3 for r, due, _ in done]
    tpot = [(r.finished_at - r.first_token_at) * 1e3 / (len(r.tokens) - 1)
            for r, _, _ in done if len(r.tokens) > 1]
    out = {"attempted": len(window), "finished": len(done)}
    start, end = times["window_start"], times["window_end"]
    emitted = sum(tokens_in(r[0], start, end) for r in recs if r is not None)
    out["tokens_per_s"] = emitted / (end - start)
    if done:
        out["tokens"] = sum(len(r.tokens) for r, _, _ in done)
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p90_ms"] = percentile(ttft, 90)
        out["tpot_p50_ms"] = percentile(tpot, 50)
        out["tpot_p90_ms"] = percentile(tpot, 90)
    late = [r[2] * 1e3 for r in recs if r is not None]
    out["feeder_late_p50_ms"] = percentile(late, 50) if late else 0.0
    out["feeder_late_max_ms"] = max(late) if late else 0.0
    return out


class CompileCounter:
    """Counts the programs compiled, or loaded from the persistent
    cache, while armed."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1


def metric_reader(name: str):
    """``bench/metrics/<name>.py``: its ``read(ctx)`` gives the metric,
    or None where the run has nothing to read it from."""
    return importlib.import_module(f"bench.metrics.{name}")


def schedule(traffic: dict, seed: int, seconds: float, vocab: int):
    """The traffic file's requests, made by the generator it names
    (``bench/traffic/<generator>.py``)."""
    gen = importlib.import_module(f"bench.traffic.{traffic['generator']}")
    return gen.schedule(traffic, seed, seconds, vocab)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
