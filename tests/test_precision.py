"""End-to-end int8 serving (paper C5 → serving tier).

The contract under test: a single ``PrecisionPolicy`` threaded from
params (QTensor) through the quant-aware matmul entry point
(``ops.quant_matmul``) into the Int8KV decode cache, with the
``fake_quant`` compute mode as the bit-faithful float oracle — int8
serving must be token-exact against it, and the int8 cache must buy a
≥2× KV-cache HBM reduction over the float32 baseline.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import quantize as qz
from repro.kernels import ops, ref
from repro.models import api
from repro.models.params import init_params
from repro.models.transformer import grow_cache
from repro.serve.kvcache import alloc_decode_cache, decode_cache_nbytes
from repro.serve.server import ContinuousBatchServer, StaticBatchServer

ARCH = "internlm2-1.8b"


@pytest.fixture(scope="module")
def setup():
    # f32 activations: the paper's C5 comparison baseline, and exact
    # fake-quant equivalence without bf16 double-rounding noise.
    cfg = dataclasses.replace(configs.get_smoke(ARCH), dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


# ---------------------------------------------------------------------------
# Policy / quantization units
# ---------------------------------------------------------------------------
def test_policy_for():
    assert qz.policy_for("float") is qz.FLOAT
    assert qz.policy_for("int8") is qz.INT8
    assert qz.policy_for(qz.INT8) is qz.INT8
    assert qz.INT8.kv_cache == "int8" and qz.INT8.weights == "int8"
    assert qz.INT8_FAKEQUANT.compute == "fake_quant"
    with pytest.raises(ValueError):
        qz.policy_for("fp4")
    with pytest.raises(AssertionError):
        qz.PrecisionPolicy(weights="int4")


def test_quant_dynamic_roundtrip():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 32) * 3, jnp.float32)
    q, s = qz.quant_dynamic(x)
    assert q.dtype == jnp.int8 and s.shape == (6,)
    err = np.abs(np.asarray(q, np.float32) * np.asarray(s)[:, None]
                 - np.asarray(x))
    # symmetric per-row quant: error bounded by half a step per element
    assert np.all(err <= np.asarray(s)[:, None] * 0.5 + 1e-7)
    # fake_quant is exactly the dequantized ints
    np.testing.assert_array_equal(
        np.asarray(qz.fake_quant_dynamic(x)),
        np.asarray(q, np.float32) * np.asarray(s)[:, None])


def test_quantize_model_params_scopes(setup):
    cfg, params = setup
    qp = qz.quantize_model_params(params, qz.INT8)
    assert isinstance(qp["blocks"]["attn"]["wq"], qz.QTensor)
    assert isinstance(qp["blocks"]["mlp"]["w_down"], qz.QTensor)
    # stacked layers keep per-layer per-channel scales
    L = cfg.n_layers
    assert qp["blocks"]["attn"]["wq"].scale.shape[0] == L
    # outside QUANT_SCOPES: float passthrough
    assert not isinstance(qp["embed"], qz.QTensor)
    assert not isinstance(qp["blocks"]["attn_norm"], qz.QTensor)
    # float policy is the identity
    assert qz.quantize_model_params(params, qz.FLOAT) is params


def test_quantize_model_params_is_idempotent(setup):
    """Handing a server already-quantized weights (so the float ones can
    be freed) serves the same weights: QTensor leaves pass through."""
    _, params = setup
    qp = qz.quantize_model_params(params, qz.INT8)
    again = qz.quantize_model_params(qp, qz.INT8)
    assert jax.tree.structure(again) == jax.tree.structure(qp)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(qp)):
        assert a is b


def test_quantize_model_params_moe_banks_stay_float():
    cfg = configs.get_smoke("dbrx-132b")
    params = init_params(cfg, jax.random.key(1))
    qp = qz.quantize_model_params(params, qz.INT8)
    assert isinstance(qp["blocks"]["attn"]["wq"], qz.QTensor)
    assert not isinstance(qp["blocks"]["moe"]["w_gate"], qz.QTensor)


def test_quant_matmul_paths():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(5, 48), jnp.float32)
    w = jnp.asarray(rng.randn(48, 24) * 0.1, jnp.float32)
    # float path: identical to the pre-refactor matmul
    np.testing.assert_array_equal(np.asarray(ops.quant_matmul(x, w)),
                                  np.asarray(x @ w))
    qw = qz._leaf_qtensor(w)
    out_native = ops.quant_matmul(x, qw, policy=qz.INT8)
    out_fake = ops.quant_matmul(x, qw, policy=qz.INT8_FAKEQUANT)
    # the fake float simulation accumulates integer-valued f32 then
    # scales — same order as the int8 kernel, so it is BIT-identical
    # while dot products stay in f32's exact-integer range (K=48 here)
    np.testing.assert_array_equal(np.asarray(out_native),
                                  np.asarray(out_fake))
    # and both approximate the float matmul at int8 fidelity
    np.testing.assert_allclose(np.asarray(out_native), np.asarray(x @ w),
                               rtol=0.2, atol=0.05)


def test_quant_matmul_calibrated_amax():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 16) * 0.1, jnp.float32)
    amax = qz.calibrate_amax([x, 2 * x, x])      # running max = 2*amax(x)
    qw = qz._leaf_qtensor(w)._replace(amax=jnp.float32(amax))
    pol = dataclasses.replace(qz.INT8, activations="calibrated")
    out = ops.quant_matmul(x, qw, policy=pol)
    xq, xs = qz.quant_dynamic(x, amax)
    expect = ref.int8_matmul_ref(xq, qw.q, xs, qw.scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)


def test_amax_observer_and_attach():
    obs = qz.AmaxObserver()
    obs.update(jnp.asarray([1.0, -3.0]))
    obs.update(jnp.asarray([2.0]))
    assert obs.amax == 3.0
    ema = qz.AmaxObserver(momentum=0.5)
    ema.update(jnp.asarray([4.0]))
    ema.update(jnp.asarray([0.0]))
    assert ema.amax == pytest.approx(2.0)

    w = jnp.ones((8, 4), jnp.float32)
    qp = {"attn": {"wq": qz._leaf_qtensor(w)}, "norm": jnp.ones((4,))}
    out = qz.attach_act_amax(qp, {"wq": 3.0})
    assert float(out["attn"]["wq"].amax) == 3.0
    assert out["attn"]["wq"].q is qp["attn"]["wq"].q
    # stacked leaves get a per-layer amax so lax.scan can slice it
    ws = jnp.ones((5, 8, 4), jnp.float32)
    out = qz.attach_act_amax({"mlp": {"w_up": qz._leaf_qtensor(ws)}},
                             {"w_up": 2.0})
    assert out["mlp"]["w_up"].amax.shape == (5,)


def test_calibrated_forward_on_stacked_model(setup):
    """Calibrated activation ranges must survive the scanned (stacked)
    param layout end-to-end: attach_act_amax broadcasts per-layer amax
    that lax.scan slices alongside the QTensor pair."""
    cfg, params = setup
    qparams = qz.quantize_model_params(params, qz.INT8)
    qparams = qz.attach_act_amax(
        qparams, {"wq": 4.0, "wk": 4.0, "wv": 4.0, "wo": 4.0,
                  "w_gate": 4.0, "w_up": 4.0, "w_down": 8.0})
    pol = dataclasses.replace(qz.INT8, activations="calibrated")
    fns = api.model_fns(cfg)
    toks = jnp.asarray(np.arange(8, dtype=np.int32)[None, :])
    logits, cache = fns.forward_prefill(cfg, qparams, {"tokens": toks}, pol)
    assert np.isfinite(np.asarray(logits)).all()
    cache = grow_cache(cfg, cache, 2)
    logits2, _ = fns.forward_decode(
        cfg, qparams, cache, jnp.asarray([3], jnp.int32),
        jnp.asarray([8], jnp.int32), policy=pol)
    assert np.isfinite(np.asarray(logits2)).all()


def test_kv_quant_roundtrip_and_policy_modes():
    rng = np.random.RandomState(3)
    k = jnp.asarray(rng.randn(2, 5, 3, 16), jnp.float32)
    kv = qz.quant_kv(k)
    assert kv.q.shape == k.shape and kv.scale.shape == (2, 5, 3)
    err = np.abs(np.asarray(qz.dequant_kv(kv)) - np.asarray(k))
    assert np.all(err <= np.asarray(kv.scale)[..., None] * 0.5 + 1e-7)
    # policy modes: passthrough / native pair / fake float
    assert qz.maybe_quant_kv(qz.FLOAT, k) is k
    native = qz.maybe_quant_kv(qz.INT8, k)
    assert isinstance(native, qz.Int8KV)
    fake = qz.maybe_quant_kv(qz.INT8_FAKEQUANT, k)
    # the fake float cache holds exactly the dequantized int8 values
    np.testing.assert_array_equal(np.asarray(qz.dequant_kv(native)),
                                  np.asarray(fake))


# ---------------------------------------------------------------------------
# Serving: token-exact int8 vs fake-quant float reference (acceptance)
# ---------------------------------------------------------------------------
def _fake_quant_reference(cfg, qparams, prompt, max_new):
    """Greedy contiguous decode of the float fake-quant simulation — the
    oracle the native int8 path must reproduce token-exactly."""
    pol = qz.INT8_FAKEQUANT
    fns = api.model_fns(cfg)
    logits, cache = fns.forward_prefill(
        cfg, qparams, {"tokens": jnp.asarray(prompt[None, :])}, pol)
    cache = grow_cache(cfg, cache, max_new + 1)
    out = [int(jnp.argmax(logits, -1)[0])]
    pos = len(prompt)
    for _ in range(max_new - 1):
        logits, cache = fns.forward_decode(
            cfg, qparams, cache, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32), policy=pol)
        out.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    return out


def test_int8_serving_token_exact(setup):
    """Continuous int8 serving (chunked pad-free prefill, slot-recycled
    Int8KV cache, ref kernel path) == fake-quant float reference."""
    cfg, params = setup
    rng = np.random.RandomState(4)
    lens = [3, 11, 7]
    budgets = [5, 4, 6]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    srv = ContinuousBatchServer(cfg, params, slots=2, max_prompt=16,
                                prefill_chunk=4, max_new_tokens=8,
                                precision="int8")
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    m = srv.run()
    assert m["precision"] == "int8"
    qparams = qz.quantize_model_params(params, qz.INT8)
    for r, p, b in zip(reqs, prompts, budgets):
        assert r.tokens == _fake_quant_reference(cfg, qparams, p, b), \
            f"rid {r.rid}: int8 serving diverged from fake-quant reference"
    # quantization is real at the numeric level: int8 logits differ from
    # float logits (greedy tokens may still coincide on a smoke model)
    fns = api.model_fns(cfg)
    t0 = jnp.asarray(prompts[1][None, :])
    lf, _ = fns.forward_prefill(cfg, params, {"tokens": t0})
    lq, _ = fns.forward_prefill(cfg, qparams, {"tokens": t0}, qz.INT8)
    assert not np.allclose(np.asarray(lf), np.asarray(lq), atol=1e-6), \
        "int8 path produced float-identical logits — quantization inactive"


def test_static_and_continuous_agree_int8(setup):
    """Scheduling still never changes tokens — now at int8."""
    cfg, params = setup
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9, 6)]
    budgets = [3, 5, 2]
    stat = StaticBatchServer(cfg, params, batch_size=2, max_prompt=16,
                             max_new_tokens=8, precision="int8")
    sreqs = stat.submit(prompts, max_new_tokens=budgets)
    ms = stat.run()
    cont = ContinuousBatchServer(cfg, params, slots=2, max_prompt=16,
                                 max_new_tokens=8, precision="int8")
    creqs = cont.submit(prompts, max_new_tokens=budgets)
    cont.run()
    assert [r.tokens for r in sreqs] == [r.tokens for r in creqs]
    assert ms["precision"] == "int8"


# ---------------------------------------------------------------------------
# KV-cache HBM: the Table-4 story on the serving tier
# ---------------------------------------------------------------------------
def test_kv_cache_hbm_reduction(setup):
    cfg, _ = setup
    f_cache = alloc_decode_cache(cfg, slots=4, capacity=40)
    q_cache = alloc_decode_cache(cfg, slots=4, capacity=40, policy=qz.INT8)
    f_bytes = decode_cache_nbytes(f_cache)
    q_bytes = decode_cache_nbytes(q_cache)
    assert f_bytes / q_bytes >= 2.0, (f_bytes, q_bytes)
    # structure: Int8KV pairs with int8 values and f32 per-entry scales
    assert isinstance(q_cache["k"], qz.Int8KV)
    assert q_cache["k"].q.dtype == jnp.int8
    assert q_cache["k"].scale.dtype == jnp.float32
    assert q_cache["k"].scale.shape == q_cache["k"].q.shape[:-1]


def test_kv_cache_bytes_arithmetic():
    from repro.serve.kvcache import kv_cache_bytes
    cfg = configs.get("internlm2-1.8b")
    fb = kv_cache_bytes(cfg, 8, 4096, 4)
    qb = kv_cache_bytes(cfg, 8, 4096, 4, precision="int8")
    hd = cfg.resolved_head_dim
    assert fb / qb == pytest.approx(4 * hd / (hd + 4))
    # ssm state is float under every precision
    ssm = configs.get("falcon-mamba-7b")
    assert kv_cache_bytes(ssm, 8, 4096, 4) == \
        kv_cache_bytes(ssm, 8, 4096, 4, precision="int8")


def test_compile_serve_decode_int8_reports_hbm_delta(setup):
    from repro.core.eon_compiler import compile_serve_decode
    cfg, params = setup
    qparams = qz.quantize_model_params(params, qz.INT8)
    art = compile_serve_decode(cfg, qparams, slots=2, capacity=12,
                               policy=qz.INT8)
    assert art.name.endswith("-int8")
    mem = art.memory
    assert mem["kv_cache_bytes_float"] / mem["kv_cache_bytes"] >= 2.0
    # the serialized executable stays runnable; decode signature is
    # (params, cache, token, position, kv_len) — index == position under
    # pad-free admission, so there is no separate write_idx operand
    fn = art.rehydrate()
    cache = alloc_decode_cache(cfg, 2, 12, qz.INT8)
    tok = jnp.zeros((2,), jnp.int32)
    ntok, _, _ = fn(qparams, cache, tok, tok, tok)
    assert ntok.shape == (2,)
