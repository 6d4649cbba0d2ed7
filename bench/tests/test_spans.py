"""The reduction of the serving loop's host spans (``bench/spans.py``):
on hand-made spans, and pinned on a trace recorded on a TPU v5e that
holds them (``internlm2-chat-spans.xplane.pb.gz``: the ``internlm2-chat``
server, warmed as a run warms it, serving four requests of 300, 40, 520
and 100 prompt tokens and 6 output tokens each, submitted from a feeder
thread, under the profiler's default options).  The older trace,
recorded before the program wrote spans, reads as a program without
them."""
import gzip
import shutil

import pytest

from bench import harness as H
from bench import spans as S
from bench import trace as T
from bench.metrics import host_ms_per_iter
from bench.tests.test_trace import FIXTURE as NO_SPANS

SPANS = H.BENCH / "tests" / "data" / "internlm2-chat-spans.xplane.pb.gz"


def _unpack(src, root):
    d = root / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(src) as a, open(d / "t.xplane.pb", "wb") as b:
        shutil.copyfileobj(a, b)
    return root


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    root = _unpack(SPANS, tmp_path_factory.mktemp("spans"))
    return root, T.load(root)["/device:TPU:0"], S.load(root)


NESTED = [S.Span(*s) for s in (
    ("serve.iteration", 0, 100), ("serve.chunk", 10, 40),
    ("serve.chunk.prepare", 10, 20), ("serve.chunk.launch", 20, 25),
    ("serve.decode.pull", 50, 90), ("serve.iteration", 120, 150),
    ("serve.decode.pull", 125, 145))]


def test_innermost_pieces():
    assert S.innermost(NESTED) == [
        (0, 10, "serve.iteration"), (10, 20, "serve.chunk.prepare"),
        (20, 25, "serve.chunk.launch"), (25, 40, "serve.chunk"),
        (40, 50, "serve.iteration"), (50, 90, "serve.decode.pull"),
        (90, 100, "serve.iteration"), (120, 125, "serve.iteration"),
        (125, 145, "serve.decode.pull"), (145, 150, "serve.iteration")]


def test_host_time_per_iteration_leaves_out_pulls():
    # (100 - 40) and (30 - 20) ns
    assert S.host_ms_per_iter(NESTED) == (2, pytest.approx(35e-6))
    assert S.host_ms_per_iter([]) == (0, 0.0)


def test_idle_split_by_innermost_span():
    chip = T.Chip(ops=[T.Event("a", 0, 15), T.Event("b", 22, 30),
                       T.Event("c", 60, 52), T.Event("d", 160, 5)])
    # idle: [15, 22) [52, 60) [112, 160)
    got = dict(S.idle_by_span(chip, NESTED))
    assert got == pytest.approx({
        "serve.chunk.prepare": 5e-9, "serve.chunk.launch": 2e-9,
        "serve.decode.pull": 28e-9, "serve.iteration": 10e-9,
        S.NO_SPAN: 18e-9})


def test_a_trace_without_spans_reads_nothing(tmp_path, monkeypatch):
    root = _unpack(NO_SPANS, tmp_path)
    assert S.load(root) == []
    monkeypatch.setattr(H, "TRACE_DIR", root)
    assert host_ms_per_iter.read({"chip": None}) is None
    assert S.load(tmp_path / "empty") == []


def test_recorded_spans(recorded):
    _, chip, spans = recorded
    names = {}
    for s in spans:
        names[s.name] = names.get(s.name, 0) + 1
    assert names == {
        "serve.iteration": 10, "serve.admit": 10, "serve.chunk": 6,
        "serve.chunk.prepare": 6, "serve.chunk.launch": 6,
        "serve.chunk.pull": 4, "serve.grow": 10, "serve.decode.prepare": 10,
        "serve.decode.launch": 10, "serve.decode.pull": 10,
        "serve.decode.emit": 10}
    # the first prompt's first 256 tokens are the warm-up prompt's: the
    # prefix cache serves them and its prefill starts at 256
    assert [(s.args["rid"], s.args["pos"], s.args["rows"]) for s in spans
            if s.name == "serve.chunk"] == [
        (2, 256, 44), (3, 0, 40), (4, 0, 256), (4, 256, 256), (4, 512, 8),
        (5, 0, 100)]
    assert S.launches(chip, spans) == {
        "serve.decode.launch": [10, 10],
        "serve.chunk.launch": [6, 6]}


def test_host_ms_per_iter_reader(recorded, monkeypatch):
    root, _, spans = recorded
    monkeypatch.setattr(H, "TRACE_DIR", root)
    n, ms = S.host_ms_per_iter(spans)
    assert n == 10
    assert host_ms_per_iter.read({"chip": None}) == pytest.approx(ms)
    assert ms == pytest.approx(3.0650089)


def test_idle_by_span(recorded):
    _, chip, spans = recorded
    idle = S.idle_by_span(chip, spans, n=100)
    total = sum(v for _, v in idle)
    assert total == pytest.approx(sum(b - a for a, b in
                                      S.idle_intervals(chip)) * 1e-9)
    assert dict(idle).get(S.NO_SPAN, 0.0) <= 0.1 * total
    top = S.idle_by_span(chip, spans, n=2)
    assert [n for n, _ in top] == ["serve.decode.pull", "serve.chunk.prepare"]
    assert top[0][1] == pytest.approx(0.010045391)
    # on the profiler's timeline as recorded, more falls in the pull
    assert S.idle_by_span(chip, spans, n=1, shift=0) == [
        ("serve.decode.pull", pytest.approx(0.014312044))]


def test_clock_bounds(recorded):
    """The device's times may move from 0.39 ms earlier to 1.51 ms
    later and keep every decode step inside its launch and pull."""
    _, chip, spans = recorded
    assert S.clock_bounds(chip, spans) == (-385136, 1512345)
    assert S.clock_bounds(T.Chip(), spans) == (0, 0)


def test_host_and_device_share_a_clock(recorded):
    """On this trace each decode-step execution starts after the start
    of a ``serve.decode.launch`` span and before the end of the
    ``serve.decode.pull`` that follows it.  (Longer traces need the
    offset of ``clock_bounds``: PERF.md §5.)"""
    _, chip, spans = recorded
    execs = sorted(m.start for m in chip.modules
                   if T.program_name(m.name) == "jit_decode_step")
    launch = [s for s in spans if s.name == "serve.decode.launch"]
    pull = [s for s in spans if s.name == "serve.decode.pull"]
    assert execs and len(execs) == len(launch) == len(pull)
    for t, a, b in zip(execs, launch, pull):
        assert a.start < t < b.end
        assert a.start < b.start
