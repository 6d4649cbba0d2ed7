"""The open-loop generator: fixed counts per phase, one set of sizes and
gaps for every seed, a seed's schedule repeats, large seeds work."""
import json

import numpy as np

from bench.harness import BENCH
from bench.traffic import open_loop

T = json.loads((BENCH / "traffic" / "chat-open.json").read_text())


def _phase(items, name):
    return [it for it in items if it.phase == name]


def test_counts_and_bounds():
    items = open_loop.schedule(T, 5, 45.0, 92544)
    win = _phase(items, "window")
    assert len(win) == round(T["rate_rps"] * 45)
    lo, hi = T["warmup_s"], T["warmup_s"] + 45
    assert all(lo <= it.due < hi for it in win)
    assert all(T["prompt"]["min"] <= len(it.prompt) <= T["prompt"]["max"]
               for it in items)
    assert all(T["output"]["min"] <= it.max_new <= T["output"]["max"]
               for it in items)
    assert all(0 <= it.prompt.min() and it.prompt.max() < 92544
               for it in items)


def test_seeds_share_the_schedule_not_the_tokens():
    a = open_loop.schedule(T, 1, 45.0, 1000)
    b = open_loop.schedule(T, 2 ** 33 + 17, 45.0, 1000)
    assert [(i.due, len(i.prompt), i.max_new, i.phase) for i in a] == \
        [(i.due, len(i.prompt), i.max_new, i.phase) for i in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the window's sizes are the quantiles, in a shuffled order
    win = [len(i.prompt) for i in _phase(a, "window")]
    assert win != sorted(win)


def test_same_seed_repeats():
    a = open_loop.schedule(T, 99, 10.0, 1000)
    b = open_loop.schedule(T, 99, 10.0, 1000)
    assert all(x.due == y.due and np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))


def test_bursty_gaps_have_their_cv():
    t = dict(T, interarrival_cv=3.0, rate_rps=4.0)
    win = _phase(open_loop.schedule(t, 3, 45.0, 1000), "window")
    gaps = np.diff([i.due for i in win])
    assert gaps.std() / gaps.mean() > 2.0


def test_tail_outputs_are_cut():
    items = open_loop.schedule(T, 5, 45.0, 1000)
    tail = _phase(items, "tail")
    assert len(tail) == round(T["rate_rps"] * T["drain_limit_s"])
    assert max(i.max_new for i in tail) <= T["tail_max_new"]


def test_window_does_not_depend_on_the_warmup():
    a = _phase(open_loop.schedule(T, 5, 45.0, 1000), "window")
    b = _phase(open_loop.schedule(dict(T, warmup_s=T["warmup_s"] + 3), 5,
                                  45.0, 1000), "window")
    assert [(round(x.due - a[0].due, 9), len(x.prompt), x.max_new)
            for x in a] == [(round(y.due - b[0].due, 9), len(y.prompt),
                             y.max_new) for y in b]
