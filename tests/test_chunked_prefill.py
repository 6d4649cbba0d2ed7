"""Chunked pad-free prefill: kernel parity, model-level chunk == one-shot,
serving token-exactness across architecture families, and scheduler
fairness.

The contracts under test (docs/scheduling.md):

* ``ops.chunk_attention`` (interpret-mode Pallas vs jnp ref): grouped-q
  GQA, position masking, per-slot ``kv_len`` bounding, pad query rows
  (ragged final chunk) returning exact zeros, in-tile Int8KV dequant;
  ``decode_attention`` is its C == 1 special case.
* ``forward_prefill_chunk`` called ceil(S / C) times reproduces the
  one-shot ``forward_prefill`` logits and cache for every family —
  uniform attention, sliding-window ring, SSM, hybrid, and enc-dec —
  including ragged final chunks (the SSM recurrence sees no pad input,
  the previously-caveated scenario, now exact).
* Chunked continuous serving is token-exact vs the unpadded one-shot
  reference for chunk sizes {1, C, S, > S} × {float, int8}.
* A slot mid-prefill never emits tokens, and a long prefill cannot
  starve active decode slots beyond the per-step token budget.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import quantize as qz
from repro.kernels import ops
from repro.models import api
from repro.models.params import init_params
from repro.models.transformer import grow_cache
from repro.serve.kvcache import alloc_decode_cache
from repro.serve.scheduler import SlotScheduler
from repro.serve.server import ContinuousBatchServer


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype="float32")
    return cfg, init_params(cfg, jax.random.key(0))


# ---------------------------------------------------------------------------
# Kernel parity: interpret-mode Pallas vs jnp ref
# ---------------------------------------------------------------------------
def _chunk_case(rng, b, c, s, hq, hkv, d, fills, reals):
    """Row i holds ``fills[i]`` live entries at positions 0..fills−1; the
    chunk's ``reals[i]`` real queries sit at the tail positions (pad
    query rows beyond get position −1)."""
    q = jnp.asarray(rng.randn(b, c, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
    pos = np.full((b, s), -1, np.int32)
    qpos = np.full((b, c), -1, np.int32)
    for i, (n, r) in enumerate(zip(fills, reals)):
        pos[i, :n] = np.arange(n)
        qpos[i, :r] = np.arange(n - r, n)
    return (q, k, v, jnp.asarray(qpos), jnp.asarray(pos),
            jnp.asarray(fills, jnp.int32))


@pytest.mark.parametrize("precision", ["float", "int8"])
@pytest.mark.parametrize("window", [0, 4])
@pytest.mark.parametrize("hkv", [4, 2, 1])     # GQA ratios 1, 2, 4
def test_chunk_attention_parity(hkv, window, precision):
    """interpret == ref across GQA ratios, windows, precisions, ragged
    per-slot kv_len, and pad query rows (which are exactly zero)."""
    rng = np.random.RandomState(0)
    b, c, s, hq, d = 3, 5, 24, 4, 16
    q, k, v, qpos, pos, kvl = _chunk_case(
        rng, b, c, s, hq, hkv, d, fills=[7, 5, 24], reals=[5, 3, 5])
    if precision == "int8":
        k, v = qz.quant_kv(k), qz.quant_kv(v)
    out_ref = ops.chunk_attention(q, k, v, qpos, pos, window=window,
                                  kv_len=kvl, force="ref")
    out_int = ops.chunk_attention(q, k, v, qpos, pos, window=window,
                                  kv_len=kvl, force="interpret")
    np.testing.assert_allclose(np.asarray(out_int), np.asarray(out_ref),
                               atol=1e-5)
    # pad query rows (ragged final chunk): exactly zero on both paths
    assert np.all(np.asarray(out_ref)[1, 3:] == 0)
    assert np.all(np.asarray(out_int)[1, 3:] == 0)


@pytest.mark.parametrize("force", ["ref", "interpret"])
def test_chunk_attention_c1_matches_decode(force):
    """C == 1 chunk attention is decode attention (same masking, same
    grouped-q math) — the degenerate chunk size the spec pins."""
    rng = np.random.RandomState(1)
    b, s, hq, hkv, d = 4, 24, 4, 2, 16
    q = jnp.asarray(rng.randn(b, 1, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
    pos = np.full((b, s), -1, np.int32)
    fills = [3, 9, 16, 24]
    for i, n in enumerate(fills):
        pos[i, :n] = np.arange(n)
    qp = jnp.asarray([n - 1 for n in fills], jnp.int32)
    kvl = jnp.asarray(fills, jnp.int32)
    chunk = ops.chunk_attention(q, k, v, qp[:, None], jnp.asarray(pos),
                                kv_len=kvl, force=force)
    dec = ops.decode_attention(q, k, v, qp, jnp.asarray(pos),
                               kv_len=kvl, force=force)
    np.testing.assert_allclose(np.asarray(chunk), np.asarray(dec),
                               atol=1e-6)


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_chunk_attention_row_tiles_match_ref(precision, monkeypatch):
    """When the (Hkv, rows, D) query tiles outgrow the kernel's VMEM
    budget the rows split into tiles, each sweeping the KV blocks anew;
    shrink the budget so 40 rows run as five tiles of 8."""
    from repro.kernels import flash_decode as fd
    monkeypatch.setattr(fd, "_Q_TILE_ELEMS", 2 * 8 * 128)
    rng = np.random.RandomState(3)
    b, c, s, hq, hkv, d = 2, 20, 24, 4, 2, 16
    q, k, v, qpos, pos, kvl = _chunk_case(
        rng, b, c, s, hq, hkv, d, fills=[20, 24], reals=[17, 20])
    k_scale = v_scale = None
    if precision == "int8":
        k, v = qz.quant_kv(k), qz.quant_kv(v)
        k, k_scale, v, v_scale = k.q, k.scale, v.q, v.scale
    g = hq // hkv
    assert fd._q_tile(c * g, hkv, d) == 8
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, c * g, d)
    rows = jnp.repeat(qpos, g, axis=1)
    got = fd._attend(qg, rows, k, v, pos, kvl, k_scale, v_scale, None,
                     window=0, block_k=fd.BLOCK_K, interpret=True)
    got = got.reshape(b, hkv, c, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, hq, d)
    if precision == "int8":
        k, v = qz.Int8KV(k, k_scale), qz.Int8KV(v, v_scale)
    want = ops.chunk_attention(q, k, v, qpos, pos, kv_len=kvl, force="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_chunk_attention_kv_len_blocks_skipped():
    """Poison the cache beyond kv_len with attendable-looking entries:
    the chunk kernel must not read them (bound is a skip, not a mask)."""
    rng = np.random.RandomState(2)
    b, c, s, hq, hkv, d = 2, 3, 32, 4, 2, 16
    q, k, v, qpos, pos, kvl = _chunk_case(
        rng, b, c, s, hq, hkv, d, fills=[6, 9], reals=[3, 3])
    clean = [ops.chunk_attention(q, k, v, qpos, pos, kv_len=kvl, force=f)
             for f in ("ref", "interpret")]
    pos_bad = np.asarray(pos).copy()
    k_bad, v_bad = np.asarray(k).copy(), np.asarray(v).copy()
    for i, n in enumerate(np.asarray(kvl)):
        pos_bad[i, n:] = 0
        k_bad[i, n:] = 100.0
        v_bad[i, n:] = 100.0
    for f, want in zip(("ref", "interpret"), clean):
        got = ops.chunk_attention(q, jnp.asarray(k_bad), jnp.asarray(v_bad),
                                  qpos, jnp.asarray(pos_bad), kv_len=kvl,
                                  force=f)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f)


# ---------------------------------------------------------------------------
# SSM ragged-chunk masking: pad steps are exact state no-ops
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,variant", [("falcon-mamba-7b", "mamba1"),
                                          ("zamba2-2.7b", "mamba2")])
def test_mamba_mask_fill_exact_state(arch, variant):
    """A masked ragged tail leaves (conv, h) where the last real token
    put them — compare against running the truncated real prefix."""
    from repro.models import ssm as ssm_mod
    cfg, params = _setup(arch)
    if variant == "mamba1":
        p = jax.tree.map(lambda x: x[0], params["blocks"])["mamba"]
        fn = ssm_mod.mamba1_layer
    else:
        p = jax.tree.map(lambda x: x[0], params["groups"])
        p = jax.tree.map(lambda x: x[0], p)["mamba"]
        fn = ssm_mod.mamba2_layer
    rng = np.random.RandomState(3)
    s, real = 8, 5
    x = jnp.asarray(rng.randn(1, s, cfg.d_model) * 0.1, jnp.float32)
    mask = jnp.asarray(np.arange(s)[None, :] < real)
    fill = jnp.asarray([real], jnp.int32)
    _, st_masked = fn(p, x, cfg, mask=mask, fill=fill)
    _, st_trunc = fn(p, x[:, :real], cfg)
    np.testing.assert_allclose(np.asarray(st_masked.conv),
                               np.asarray(st_trunc.conv), atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_masked.h),
                               np.asarray(st_trunc.h), atol=1e-5)


# ---------------------------------------------------------------------------
# Model level: chunked prefill == one-shot prefill
# ---------------------------------------------------------------------------
def _chunked_prefill(cfg, params, prompt, chunk, capacity, policy=None):
    """Drive forward_prefill_chunk over a whole prompt; returns the last
    real row's logits and the resulting cache."""
    fns = api.model_fns(cfg)
    cache = alloc_decode_cache(cfg, 1, capacity, policy)
    s, p, last = len(prompt), 0, None
    while p < s:
        r = min(chunk, s - p)
        toks = np.zeros((1, chunk), np.int32)
        poss = np.full((1, chunk), -1, np.int32)
        toks[0, :r] = prompt[p:p + r]
        poss[0, :r] = np.arange(p, p + r, dtype=np.int32)
        logits, cache = fns.forward_prefill_chunk(
            cfg, params, cache, jnp.asarray(toks), jnp.asarray(poss),
            policy=policy, kv_len=jnp.asarray([p + chunk], jnp.int32))
        last = np.asarray(logits)[0, r - 1]
        p += r
    return last, cache


# the uniform arch sweeps every chunk size {1, C, S, > S}; the slower
# trunks pin the two interesting shapes (ragged tail, single ragged
# chunk) — the serving tests below re-cover chunk == 1 end to end.
@pytest.mark.parametrize("arch,chunk", [
    ("internlm2-1.8b", 1), ("internlm2-1.8b", 4),
    ("internlm2-1.8b", 11), ("internlm2-1.8b", 16),
    ("gemma3-4b", 4), ("gemma3-4b", 16),
    ("falcon-mamba-7b", 4), ("falcon-mamba-7b", 16),
    ("zamba2-2.7b", 4), ("zamba2-2.7b", 16),
])
def test_chunked_prefill_matches_oneshot(arch, chunk):
    """ceil(S/C) chunk steps == one full prefill: same greedy token and
    logits to float tolerance, for every trunk family and chunk size
    (11 == S exercises the exact-fit path, 16 > S the single ragged
    chunk, 4 the ragged-tail path the SSM masking must get right)."""
    cfg, params = _setup(arch)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, 11).astype(np.int32)
    ref_logits, _ = api.model_fns(cfg).forward_prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :])})
    ref = np.asarray(ref_logits)[0]
    last, _ = _chunked_prefill(cfg, params, prompt, chunk, capacity=24)
    np.testing.assert_allclose(last, ref, atol=2e-4)
    assert int(last.argmax()) == int(ref.argmax())


def test_chunked_prefill_encdec_matches_oneshot():
    """The enc-dec decoder prefills in chunks too: encoder runs once
    (init_chunk_cache), decoder chunks attend self prefix + cross KV."""
    from repro.models import encdec
    cfg, params = _setup("seamless-m4t-large-v2")
    fns = api.model_fns(cfg)
    rng = np.random.RandomState(1)
    s, chunk, cap = 10, 4, 16
    enc = jnp.asarray(rng.randn(1, s // cfg.enc_seq_divisor, cfg.d_model)
                      * 0.1, jnp.float32)
    prompt = rng.randint(0, cfg.vocab_size, s).astype(np.int32)
    ref_logits, _ = fns.forward_prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :]),
                      "enc_embeddings": enc})
    cache = encdec.init_chunk_cache(cfg, params, enc, cap)
    p, last = 0, None
    while p < s:
        r = min(chunk, s - p)
        toks = np.zeros((1, chunk), np.int32)
        poss = np.full((1, chunk), -1, np.int32)
        toks[0, :r] = prompt[p:p + r]
        poss[0, :r] = np.arange(p, p + r, dtype=np.int32)
        logits, cache = fns.forward_prefill_chunk(
            cfg, params, cache, jnp.asarray(toks), jnp.asarray(poss),
            kv_len=jnp.asarray([p + chunk], jnp.int32))
        last = np.asarray(logits)[0, r - 1]
        p += r
    ref = np.asarray(ref_logits)[0]
    np.testing.assert_allclose(last, ref, atol=2e-4)
    assert int(last.argmax()) == int(ref.argmax())


# ---------------------------------------------------------------------------
# Serving: token-exact across chunk sizes × precisions × families
# ---------------------------------------------------------------------------
def _reference_decode(cfg, params, prompt, max_new):
    fns = api.model_fns(cfg)
    logits, cache = fns.forward_prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :])})
    cache = grow_cache(cfg, cache, max_new + 1)
    out = [int(jnp.argmax(logits, -1)[0])]
    pos = len(prompt)
    for _ in range(max_new - 1):
        logits, cache = fns.forward_decode(
            cfg, params, cache, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        out.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    return out


_LENS, _BUDGETS = (4, 12, 7), (4, 3, 5)


def _workload(cfg, seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
            for n in _LENS]


@functools.lru_cache(maxsize=None)
def _references(arch, seed=5):
    """One-shot unpadded reference streams, shared across the chunk-size
    parametrization (each serving run compares against the same oracle)."""
    cfg, params = _setup(arch)
    return [_reference_decode(cfg, params, p, b)
            for p, b in zip(_workload(cfg, seed), _BUDGETS)]


@pytest.mark.parametrize("chunk", [1, 4, 16])   # 1; C == S of prompt 0
#                                               # (and divides 12); > all S
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-4b",
                                  "falcon-mamba-7b", "zamba2-2.7b"])
def test_chunked_serving_token_exact_float(arch, chunk):
    """ACCEPTANCE: chunked continuous serving — prefill interleaved with
    decode, no pad rows — is token-exact vs the one-shot unpadded
    reference on attention, ring, SSM, and hybrid architectures.  The
    SSM/hybrid rows are the previously-caveated scenario, now exact."""
    cfg, params = _setup(arch)
    prompts = _workload(cfg)
    srv = ContinuousBatchServer(cfg, params, slots=2, max_prompt=16,
                                prefill_chunk=chunk, max_new_tokens=8)
    reqs = srv.submit(prompts, max_new_tokens=list(_BUDGETS))
    srv.run()
    for r, ref in zip(reqs, _references(arch)):
        assert r.tokens == ref, \
            f"{arch} chunk={chunk} rid {r.rid} diverged"


@pytest.mark.parametrize("chunk", [1, 4, 16])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-4b",
                                  "zamba2-2.7b"])
def test_chunked_serving_token_exact_int8(arch, chunk):
    """Native int8 chunked serving == the fake-quant float oracle
    through the same chunk schedule (the oracle's float cache holds
    exactly the dequantized int8 values at every chunk write)."""
    cfg, params = _setup(arch)
    prompts = _workload(cfg, seed=6)
    kw = dict(slots=2, max_prompt=16, prefill_chunk=chunk,
              max_new_tokens=8)
    srv = ContinuousBatchServer(cfg, params, precision="int8", **kw)
    reqs = srv.submit(prompts, max_new_tokens=list(_BUDGETS))
    srv.run()
    fq = ContinuousBatchServer(cfg, params, precision="int8_fakequant",
                               **kw)
    freqs = fq.submit(prompts, max_new_tokens=list(_BUDGETS))
    fq.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in freqs], \
        f"{arch} chunk={chunk}: int8 diverged from fake-quant oracle"


# ---------------------------------------------------------------------------
# Scheduler: no mid-prefill emission, no decode starvation
# ---------------------------------------------------------------------------
def test_prefilling_slot_is_not_active():
    """A slot mid-prefill is never in the decode set (so it can never
    emit a token), and flips active only when its prompt is exhausted."""
    s = SlotScheduler(1)
    slot = s.slots[0]
    slot.occupy(0, np.arange(9, dtype=np.int32), 4)
    assert s.prefilling_slots() == [slot]
    assert s.active_slots() == []
    slot.chunk_pos = 9
    slot.begin_decode()
    assert s.prefilling_slots() == []
    assert s.active_slots() == [slot]


def test_long_prefill_does_not_starve_decode():
    """With a one-chunk-per-step budget, a 20-token prompt admitted next
    to an active slot must not delay that slot's tokens: the short
    request finishes after exactly its max_new − 1 decode steps, the
    long one emits nothing until its prefill completes, and both are
    token-exact under the interleaving."""
    cfg, params = _setup("internlm2-1.8b")
    rng = np.random.RandomState(7)
    short = rng.randint(0, cfg.vocab_size, 4).astype(np.int32)
    long = rng.randint(0, cfg.vocab_size, 20).astype(np.int32)
    srv = ContinuousBatchServer(cfg, params, slots=2, max_prompt=24,
                                prefill_chunk=4, prefill_token_budget=4,
                                max_new_tokens=10)
    ra, rb = srv.submit([short, long], max_new_tokens=[10, 6])
    srv.run()
    # short request decoded every step: 1 prefill token + 9 decode steps
    assert ra.finished_step == 9, \
        f"short request starved behind the long prefill ({ra.finished_step})"
    # the long prompt (5 chunks, 1 chunk/step) emits its first token
    # only after the short slot has produced several decode tokens
    assert rb.first_token_at > ra.first_token_at
    assert len(rb.tokens) == 6
    # interleaving never corrupts either stream
    assert ra.tokens == _reference_decode(cfg, params, short, 10)
    assert rb.tokens == _reference_decode(cfg, params, long, 6)
