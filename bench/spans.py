"""Reduction of the serving loop's host spans, read with nothing but
``jax.profiler.ProfileData``.

``PagedBatchServer`` wraps each part of its loop in a
``jax.profiler.TraceAnnotation`` named ``serve.*`` (docs/serving.md,
"Observability").  The profiler writes them into the same ``.xplane.pb``
as the device events, on the host plane's line of the thread that ran
them and on the device planes' clock.  A span is recorded only if it
began and ended while the profiler ran, so every recorded span lies
wholly inside the traced window.  From the serving thread's spans the
reduction takes:

* host time per loop iteration outside the token pulls: the host work
  that the synchronous loop puts in series with the device;
* each device idle interval split by the innermost span that covers
  each part of it: what the host was doing while the chip waited.

One timeline is not one clock: in 4 s traces of the ``internlm2-chat``
cell on a TPU v5e the device's events sat 0.3 to 2.9 ms early against
the host's (PERF.md §5), as long as a whole idle gap.  So the split
first moves the device's events by an offset bounded by causality: each
decode-step execution starts after its ``serve.decode.launch`` span
began and ends before its ``serve.decode.pull`` span ended.

    python3 -m bench.spans <trace dir>

prints both for the newest trace under the directory, with the clock
bounds, the split at each of them, and the counts of step launches
against program executions, as one JSON object.
"""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from bench import trace as T

PREFIX = "serve."
ITERATION = "serve.iteration"
NO_SPAN = "(no span)"
# (launch span, the program it dispatches)
LAUNCHES = (("serve.decode.launch", "jit_decode_step"),
            ("serve.chunk.launch", "jit_paged_chunk_step"))


@dataclass
class Span:
    name: str
    start: int        # ns
    end: int          # ns
    args: Dict[str, object] = field(default_factory=dict)


def load(path: Path) -> List[Span]:
    """``serve.*`` spans of the serving thread (the host line holding
    the most ``serve.iteration`` spans) in the newest ``.xplane.pb``
    under ``path``, by start; empty where there are none."""
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return []
    data = ProfileData.from_file(str(files[-1]))
    best: List[Span] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [Span(e.name, int(e.start_ns),
                          int(e.start_ns) + int(e.duration_ns),
                          dict(e.stats))
                     for e in line.events if e.name.startswith(PREFIX)]
            if _iterations(spans) > _iterations(best):
                best = spans
    return sorted(best, key=lambda s: (s.start, -s.end))


def _iterations(spans: List[Span]) -> int:
    return sum(1 for s in spans if s.name == ITERATION)


def host_ms_per_iter(spans: List[Span]) -> Tuple[int, float]:
    """(iterations, mean ms): each ``serve.iteration`` span's duration
    less the ``serve.*.pull`` spans inside it."""
    pulls = [s for s in spans if s.name.endswith(".pull")]
    its = [s for s in spans if s.name == ITERATION]
    total = 0
    for it in its:
        inner = sum(p.end - p.start for p in pulls
                    if p.start >= it.start and p.end <= it.end)
        total += it.end - it.start - inner
    return len(its), (total / len(its) * 1e-6 if its else 0.0)


def innermost(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """The thread's timeline as disjoint (start, end, name) pieces, each
    named after the innermost span covering it (spans of one thread
    nest); time outside every span is left out."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Span] = []
    at = 0

    def close_until(t: int) -> None:
        nonlocal at
        while stack and stack[-1].end <= t:
            top = stack.pop()
            out.append((at, top.end, top.name))
            at = top.end

    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        close_until(s.start)
        if stack:
            out.append((at, s.start, stack[-1].name))
        stack.append(s)
        at = s.start
    close_until(sys.maxsize)
    return [p for p in out if p[1] > p[0]]


def idle_intervals(chip: T.Chip) -> List[Tuple[int, int]]:
    """Gaps between the device's busy intervals (the union of its
    operations), from its first operation to its last."""
    busy = T.union([(o.start, o.start + o.dur) for o in chip.ops])
    return [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]


def clock_bounds(chip: T.Chip, spans: List[Span]) -> Tuple[int, int]:
    """(least, most) ns to add to the device's times so that every
    decode-step execution starts after the start of its launch span and
    ends before the end of its pull span; (0, 0) where no step is in
    both.  A step execution is matched to the launch and the pull that
    follows it by its midpoint, which a few ms of offset cannot move
    out of a 38 ms step."""
    launch_name, program = LAUNCHES[0]
    pulls = [s for s in spans if s.name == "serve.decode.pull"]
    execs = [m for m in chip.modules if T.program_name(m.name) == program]
    least, most = [], []
    for launch in (s for s in spans if s.name == launch_name):
        pull = next((p for p in pulls if p.start >= launch.start), None)
        if pull is None:
            continue
        for m in execs:
            if launch.start < m.start + m.dur // 2 < pull.end:
                least.append(launch.start - m.start)
                most.append(pull.end - (m.start + m.dur))
    return (max(least), min(most)) if least else (0, 0)


def idle_by_span(chip: T.Chip, spans: List[Span], n: int = 10,
                 shift: Optional[int] = None) -> List[Tuple[str, float]]:
    """Device idle seconds by the innermost ``serve.*`` span that the
    serving thread was in, top ``n``; ``(no span)`` for idle time
    outside every span.  The device's times move by ``shift`` ns,
    by default halfway between the ``clock_bounds``."""
    if shift is None:
        shift = sum(clock_bounds(chip, spans)) // 2
    pieces = innermost(spans)
    tot: Dict[str, float] = {}
    j = 0
    for a, b in ((a + shift, b + shift) for a, b in idle_intervals(chip)):
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            part = min(e, b) - max(s, a)
            if part > 0:
                tot[name] = tot.get(name, 0.0) + part * 1e-9
                covered += part
            k += 1
        if b - a > covered:
            tot[NO_SPAN] = tot.get(NO_SPAN, 0.0) + (b - a - covered) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def launches(chip: T.Chip, spans: List[Span]) -> Dict[str, List[int]]:
    """{launch span: [spans, executions of the program it launches]}."""
    progs = T.programs(chip)
    return {name: [sum(1 for s in spans if s.name == name),
                   progs.get(prog, (0, 0.0))[0]]
            for name, prog in LAUNCHES}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    chips = T.load(Path(args[0]))
    if not chips:
        print(f"no TPU plane in the trace under {args[0]}", file=sys.stderr)
        return 1
    chip = chips[sorted(chips)[0]]
    spans = load(Path(args[0]))
    iters, host_ms = host_ms_per_iter(spans)
    least, most = clock_bounds(chip, spans)
    idle = idle_by_span(chip, spans, n=1000)
    idle_s = sum(v for _, v in idle)
    print(json.dumps({
        "iterations": iters, "host_ms_per_iter": host_ms,
        "idle_s": idle_s,
        "idle_in_spans_share": (1 - dict(idle).get(NO_SPAN, 0.0) / idle_s
                                if idle_s else None),
        "clock_bounds_ms": [least * 1e-6, most * 1e-6],
        "idle_by_span": idle[:10],
        "idle_by_span_at_bounds": [idle_by_span(chip, spans, 10, least),
                                   idle_by_span(chip, spans, 10, most)],
        "launches": launches(chip, spans)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
