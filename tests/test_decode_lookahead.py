"""One-step decode lookahead in ``PagedBatchServer.run`` (docs/serving.md,
"One-step decode lookahead").

The loop launches decode step n+1 before it pulls step n's tokens, with
step n's token output as step n+1's token operand on the device, and
drains (pulls step n first) only where a slot enters decode or growth
must preempt.  The checks:

* streams stay token-exact under concurrent requests and staggered joins
  (float against the unbatched reference; int8 against the fake-quant
  engine, and against the one-shot fake-quant reference up to its first
  near tie), and most steps do launch ahead;
* a slot whose ``eos`` is found after its next step was launched ends
  at that token, and the slot's next tenant is token-exact;
* a pool too small for the workload preempts only after a drain and
  stays token-exact;
* after the benchmark's warm-up, which never launches ahead, a run that
  takes every path compiles and loads no program.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import quantize as qz
from repro.models import api
from repro.models.params import init_params
from repro.models.transformer import grow_cache
from repro.serve.server import PagedBatchServer

ARCHS = ("internlm2-1.8b", "zamba2-2.7b")
# below this top-1/top-2 margin the one-shot int8 reference's choice is a
# tie the served int8 path may break either way (tests/test_precision.py)
TAU = 1e-2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype="float32")
    return cfg, init_params(cfg, jax.random.key(0))


def _reference(cfg, params, prompt, max_new, policy=None):
    """Unbatched greedy decode (the whole prompt in one prefill, then one
    token per step): its tokens and each step's top-1/top-2 margin."""
    fns = api.model_fns(cfg)
    logits, cache = fns.forward_prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :])}, policy)
    cache = grow_cache(cfg, cache, max_new + 1)
    out, margins = [], []

    def take(lg):
        lg = np.asarray(lg[0])
        top2 = np.sort(lg)[-2:]
        out.append(int(lg.argmax()))
        margins.append(float(top2[1] - top2[0]))

    take(logits)
    for _ in range(max_new - 1):
        logits, cache = fns.forward_decode(
            cfg, params, cache, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([len(prompt) + len(out) - 1], jnp.int32),
            policy=policy)
        take(logits)
    return out, margins


def _prompts(cfg, lens, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# five requests through three slots, prompts of one to four chunks under
# a one-chunk budget: slots enter decode at different iterations, and two
# requests wait for a slot to free
LENS, BUDGETS = (5, 14, 3, 9, 11), (9, 6, 12, 7, 8)
KW = dict(slots=3, max_prompt=16, prefill_chunk=4, max_new_tokens=12,
          block_size=8)


@pytest.mark.parametrize("precision", ["float", "int8"])
@pytest.mark.parametrize("arch", ARCHS)
def test_streams_exact_with_staggered_joins(arch, precision):
    cfg, params = _setup(arch)
    prompts = _prompts(cfg, LENS, seed=21)
    srv = PagedBatchServer(cfg, params, precision=precision, **KW)
    reqs = srv.submit(prompts, list(BUDGETS))
    m = srv.run()
    assert m["decode_ahead_share"] > 0.5, m["decode_ahead_share"]
    assert len({r.admitted_step for r in reqs}) > 2, "no staggered joins"
    if precision == "float":
        for r, p, b in zip(reqs, prompts, BUDGETS):
            assert r.tokens == _reference(cfg, params, p, b)[0], \
                f"{arch} rid {r.rid} diverged"
        return
    fq = PagedBatchServer(cfg, params, precision="int8_fakequant", **KW)
    freqs = fq.submit(prompts, list(BUDGETS))
    fq.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in freqs]
    qparams = qz.quantize_model_params(params, qz.INT8)
    for r, p, b in zip(reqs, prompts, BUDGETS):
        assert len(r.tokens) == b
        want, margins = _reference(cfg, qparams, p, b, qz.INT8_FAKEQUANT)
        for i, (got, ref_tok, margin) in enumerate(zip(r.tokens, want,
                                                       margins)):
            if margin < TAU:
                break
            assert got == ref_tok, \
                f"{arch} int8 rid {r.rid} step {i} at margin {margin:.2e}"


@pytest.mark.parametrize("arch", ARCHS)
def test_eos_found_after_next_launch(arch):
    """``eos`` is a token the first request's reference emits at step
    k >= 2, when step k+1 is already launched: the stream ends at it,
    and the next tenant of the slot (one slot, a pool of one slot's
    blocks, so it reuses the blocks the discarded step wrote into) is
    token-exact."""
    cfg, params = _setup(arch)
    a, b, c = _prompts(cfg, (6, 9, 4), seed=1)
    ref_a = _reference(cfg, params, a, 10)[0]
    ref_b = _reference(cfg, params, b, 8)[0]
    ref_c = _reference(cfg, params, c, 7)[0]
    k = next(i for i in range(2, 8)
             if ref_a[i] not in ref_a[:i] + ref_b + ref_c)
    eos = ref_a[k]
    srv = PagedBatchServer(cfg, params, slots=1, max_prompt=16,
                           prefill_chunk=4, max_new_tokens=10,
                           block_size=8, pool_blocks=4, eos_id=eos)
    reqs = srv.submit([a, b, c], [10, 8, 7])
    m = srv.run()
    assert m["decode_ahead_share"] > 0.5
    assert [r.tokens for r in reqs] == [ref_a[:k + 1], ref_b, ref_c]
    # every block is back: free, or held by the prefix cache alone
    mgr = srv.manager
    assert mgr.free_blocks + mgr.registry_size() == srv.pool_blocks


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_forced_preemption_drains_first(precision):
    """A pool of 8 blocks under 3 slots x 4 blocks preempts; each
    preemption comes after a drain, so the streams stay token-exact
    (float: the reference; int8: the fake-quant engine), and both the
    drained and the ahead launches are counted."""
    cfg, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, (14, 15, 13), seed=5)
    budgets = [12, 12, 12]
    kw = dict(slots=3, max_prompt=16, prefill_chunk=4, max_new_tokens=12,
              block_size=8, pool_blocks=8, prefix_cache=False)
    srv = PagedBatchServer(cfg, params, precision=precision, **kw)
    reqs = srv.submit(prompts, budgets)
    m = srv.run()
    assert m["preemptions"] > 0, "pool never ran dry"
    ahead = round(m["decode_ahead_share"] * m["decode_steps"])
    drained = m["decode_steps"] - ahead
    assert ahead > 0 and drained > m["preemptions"], (ahead, drained)
    if precision == "float":
        refs = [_reference(cfg, params, p, n)[0]
                for p, n in zip(prompts, budgets)]
    else:
        fq = PagedBatchServer(cfg, params, precision="int8_fakequant", **kw)
        fq.submit(prompts, budgets)
        fq.run()
        refs = [r.tokens for r in fq.requests.values()]
    assert [r.tokens for r in reqs] == refs


@pytest.mark.parametrize("use_artifact", [False, True])
def test_no_program_after_warm_up(use_artifact):
    """Warm a fresh server as the chip benchmark does (a prompt of one
    chunk and one token and one of three, two tokens each: no step is
    launched ahead), then serve a run with ahead launches, joins, drains,
    an ``eos`` and preemptions: nothing is compiled or loaded."""
    cfg, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, (14, 15, 13, 6), seed=5)
    budgets = [12, 12, 12, 6]
    eos = _reference(cfg, params, prompts[3], 6)[0][3]
    chunk = 4
    srv = PagedBatchServer(cfg, params, slots=3, max_prompt=16,
                           prefill_chunk=chunk, max_new_tokens=12,
                           block_size=8, pool_blocks=8, prefix_cache=False,
                           eos_id=eos, use_artifact=use_artifact)
    rng = np.random.default_rng(0)
    srv.submit([rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
                for n in (chunk + 1, 3)], [2, 2])
    warm = srv.run()
    assert warm["decode_steps"] > 0 and warm["decode_ahead_share"] == 0.0
    compiles = []

    def on(event, duration, **kw):
        if event == COMPILE_EVENT:
            compiles.append(kw)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        reqs = srv.submit(prompts, budgets)
        m = srv.run()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert m["preemptions"] > 0 and m["decode_ahead_share"] > 0
    assert reqs[3].tokens[-1] == eos and len(reqs[3].tokens) < budgets[3]
    assert compiles == []
