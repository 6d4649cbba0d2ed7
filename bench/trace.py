"""Reduction of a profiler trace to device time, read with nothing but
``jax.profiler.ProfileData``.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``).  Its
``XLA Modules`` line has one event per execution of a compiled program
(``jit_<name>(<id>)``), and its ``XLA Ops`` line one event per operation
inside it, Pallas kernels among them.  From these the reduction takes:

* busy seconds: the union of the operations' intervals on each chip;
* per program: executions and device seconds (the program's module
  events), under the program's name without its id;
* per kernel and program: calls and device seconds of the operations
  whose name a caller asks about, inside that program's executions;
* the operations that took the most time, and the longest idle gaps,
  each gap named after the program that ran next.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

MODULES, OPS = "XLA Modules", "XLA Ops"
_ID = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r" ([a-z][\w\-.]*)\(")
_CONTAINER = re.compile(r" (while|conditional)\(")


def program_name(event_name: str) -> str:
    return _ID.sub("", event_name)


@dataclass
class Event:
    name: str
    start: int        # ns
    dur: int          # ns
    module: str = ""  # program the op ran in (ops only)


@dataclass
class Chip:
    modules: List[Event] = field(default_factory=list)
    ops: List[Event] = field(default_factory=list)


def load(path: Path) -> Dict[str, Chip]:
    """Device planes of the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = ProfileData.from_file(str(files[-1]))
    chips: Dict[str, Chip] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        chip = chips.setdefault(plane.name, Chip())
        for line in plane.lines:
            if line.name not in (MODULES, OPS):
                continue
            evs = [Event(e.name, int(e.start_ns), int(e.duration_ns))
                   for e in line.events]
            (chip.modules if line.name == MODULES else chip.ops).extend(evs)
    for chip in chips.values():
        attribute(chip)
    return chips


def attribute(chip: Chip) -> None:
    """Name each op's program: the module execution that contains it."""
    mods = sorted(chip.modules, key=lambda e: e.start)
    chip.ops.sort(key=lambda e: e.start)
    j = 0
    for op in chip.ops:
        while j < len(mods) and mods[j].start + mods[j].dur < op.start:
            j += 1
        if j < len(mods) and mods[j].start <= op.start:
            op.module = program_name(mods[j].name)


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(chip: Chip) -> int:
    return sum(e - s for s, e in union([(o.start, o.start + o.dur)
                                        for o in chip.ops]))


def programs(chip: Chip) -> Dict[str, Tuple[int, float]]:
    """{program: (executions, device seconds)}."""
    out: Dict[str, Tuple[int, float]] = {}
    for m in chip.modules:
        n, s = out.get(program_name(m.name), (0, 0.0))
        out[program_name(m.name)] = (n + 1, s + m.dur * 1e-9)
    return out


def kernel(chip: Chip, pattern: str, program: str) -> Tuple[int, float]:
    """(calls, device seconds) of ops matching ``pattern`` (a regular
    expression on the op name) inside executions of ``program``."""
    rx = re.compile(pattern)
    hits = [o for o in chip.ops if o.module == program and rx.search(o.name)]
    return len(hits), sum(o.dur for o in hits) * 1e-9


def short_name(op_name: str) -> str:
    """``%convert.29 = bf16[...] convert(...)`` -> ``%convert.29 convert``:
    the instruction's name and its opcode."""
    head, _, rest = op_name.partition(" = ")
    m = _OPCODE.search(rest)
    return f"{head} {m.group(1)}" if m else head


def top_ops(chip: Chip, n: int = 10) -> List[Tuple[str, float]]:
    """The instructions that took the most device time, summed over
    their executions.  Loops and conditionals are left out: their time
    is that of the instructions inside them."""
    tot: Dict[str, float] = {}
    for o in chip.ops:
        if _CONTAINER.search(o.name):
            continue
        key = f"{o.module}/{short_name(o.name)}"
        tot[key] = tot.get(key, 0.0) + o.dur * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(chip: Chip, n: int = 10) -> List[Tuple[str, float]]:
    """Longest gaps between busy intervals, each named after the
    program whose op ends it."""
    ops = sorted(chip.ops, key=lambda o: o.start)
    spans = union([(o.start, o.start + o.dur) for o in ops])
    starts = {o.start: o for o in ops}
    gaps = []
    for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
        nxt = starts.get(s1)
        label = f"before {nxt.module or nxt.name}" if nxt else "gap"
        gaps.append((label, (s1 - e0) * 1e-9))
    return sorted(gaps, key=lambda g: -g[1])[:n]
