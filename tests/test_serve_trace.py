"""Observability of the paged serving loop (docs/serving.md,
"Observability").

The paged server runs under ``jax.profiler.trace`` and the trace is read
back with ``ProfileData``: every ``serve.*`` span is there, nested as
the loop nests them, with the request ids and counts it carries; one
``serve.decode.launch`` and one ``serve.decode.pull`` per decode step
and one ``serve.chunk`` per prefill chunk.  A launch may come before
the pull of the step before it: its ``ahead`` flag says so, and a
preemption never has a step in flight.  The request stamps hold in the
same runs: admission after submission, one non-decreasing host time per
emitted token, the first of them the first-token time.  Each check runs
on a plain workload and on one whose pool is smaller than the slots'
rectangle, so that preempt-and-recompute re-admits and re-prefills
requests.
"""
import dataclasses

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.models.params import init_params
from repro.serve.server import PagedBatchServer

PARENT = {
    "serve.iteration": None,
    "serve.admit": "serve.iteration",
    "serve.chunk": "serve.iteration",
    "serve.chunk.prepare": "serve.chunk",
    "serve.chunk.launch": "serve.chunk",
    "serve.chunk.pull": "serve.chunk",
    "serve.grow": "serve.iteration",
    "serve.decode.prepare": "serve.iteration",
    "serve.decode.launch": "serve.iteration",
    "serve.decode.pull": "serve.iteration",
    "serve.decode.emit": "serve.iteration",
    "serve.submit": None,
}

RUNS = {
    # five requests through two slots: recycling, multi-chunk prompts
    "plain": dict(lens=(5, 12, 9, 3, 16), budgets=(6, 4, 8, 5, 3),
                  kw=dict(slots=2, max_prompt=16, prefill_chunk=4,
                          max_new_tokens=8, block_size=8)),
    # a pool of 8 blocks under 3 slots x 4 blocks: preemption
    "preempt": dict(lens=(14, 15, 13), budgets=(12, 12, 12),
                    kw=dict(slots=3, max_prompt=16, prefill_chunk=4,
                            max_new_tokens=12, block_size=8, pool_blocks=8,
                            prefix_cache=False)),
}


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(configs.get_smoke("internlm2-1.8b"),
                              dtype="float32")
    return cfg, init_params(cfg, jax.random.key(0))


def _spans(trace_dir):
    """[(name, start, end, args, parent name)] of every host thread."""
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns), dict(e.stats))
                          for e in line.events
                          if e.name.startswith("serve.")),
                         key=lambda e: (e[1], -e[2]))
            stack = []
            for name, s, e, args in evs:
                while stack and stack[-1][2] <= s:
                    stack.pop()
                out.append((name, s, e, args, stack[-1][0] if stack
                            else None))
                stack.append((name, s, e))
    return out


@pytest.fixture(scope="module", params=sorted(RUNS))
def traced(request, model, tmp_path_factory):
    cfg, params = model
    run = RUNS[request.param]
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in run["lens"]]
    srv = PagedBatchServer(cfg, params, **run["kw"])
    srv.submit(prompts[:1], [2])     # compile outside the trace
    srv.run()
    trace_dir = tmp_path_factory.mktemp(f"trace_{request.param}")
    with jax.profiler.trace(str(trace_dir)):
        reqs = srv.submit(prompts, list(run["budgets"]))
        m = srv.run()
    if request.param == "preempt":
        assert m["preemptions"] > 0, "pool never ran dry"
    return reqs, m, _spans(trace_dir)


def test_every_span_nested_as_the_loop_nests(traced):
    _, _, spans = traced
    assert {s[0] for s in spans} == set(PARENT)
    for name, _, _, _, parent in spans:
        assert parent == PARENT[name], (name, parent)


def test_chunk_spans_carry_request_ids(traced):
    reqs, _, spans = traced
    chunks = [s[3] for s in spans if s[0] == "serve.chunk"]
    assert {a["rid"] for a in chunks} == {r.rid for r in reqs}
    assert all(0 < a["rows"] and 0 <= a["pos"] for a in chunks)
    (submit,) = [s[3] for s in spans if s[0] == "serve.submit"]
    assert submit == {"rid": reqs[0].rid, "n": len(reqs)}


def test_span_counts_match_the_counters(traced):
    reqs, m, spans = traced

    def count(name):
        return sum(1 for s in spans if s[0] == name)

    def total(name, arg):
        return sum(s[3][arg] for s in spans if s[0] == name)

    assert count("serve.decode.launch") == m["decode_steps"]
    assert count("serve.decode.pull") == m["decode_steps"]
    assert count("serve.chunk") == m["prefill_chunks"]
    assert count("serve.chunk.launch") == m["prefill_chunks"]
    # a preempted request is admitted again; its re-prefill ends in a
    # pull unless it is preempted again before its last chunk
    admissions = len(reqs) + m["preemptions"]
    assert total("serve.admit", "admitted") == admissions
    assert len(reqs) <= count("serve.chunk.pull") <= admissions
    assert total("serve.grow", "preempted") == m["preemptions"]
    # every budget is above one token: each request ends at a decode step
    assert total("serve.decode.emit", "finished") == len(reqs)


def test_launches_carry_ahead(traced):
    """Each decode launch says whether the step before it was still
    unpulled; the flags sum to the server's share times its steps, and
    both kinds of launch occur."""
    _, m, spans = traced
    ahead = [s[3]["ahead"] for s in spans if s[0] == "serve.decode.launch"]
    assert set(ahead) == {0, 1}
    assert sum(ahead) == round(m["decode_ahead_share"] * m["decode_steps"])


def test_preemption_waits_for_the_step_in_flight(traced):
    """Growth that has to preempt comes after the pull of every decode
    step launched before it: a preempted request is re-prefilled over
    prompt ++ generated, which then holds its last token."""
    _, m, spans = traced
    grows = [s for s in spans if s[0] == "serve.grow" and s[3]["preempted"]]
    assert sum(g[3]["preempted"] for g in grows) == m["preemptions"]
    for g in grows:
        launched = sum(1 for s in spans
                       if s[0] == "serve.decode.launch" and s[1] < g[1])
        pulled = sum(1 for s in spans
                     if s[0] == "serve.decode.pull" and s[2] <= g[1])
        assert launched == pulled, (launched, pulled)


def test_request_stamps(traced):
    reqs, _, _ = traced
    for r in reqs:
        assert r.done
        assert r.admitted_at >= r.submitted_at
        assert len(r.token_times) == len(r.tokens)
        assert r.token_times[0] == r.first_token_at
        assert np.all(np.diff(r.token_times) >= 0)
        assert r.finished_at >= r.token_times[-1]
