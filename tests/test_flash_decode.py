"""Flash-decode kernel: interpret-mode Pallas vs jnp ref, and the
serving tier running end-to-end through the kernel.

The contract under test (kernels/flash_decode.py):

* grouped-q GQA in-kernel (KV never repeated), any Hq/Hkv ratio;
* position-validity masking identical to the ref (−1 invalid,
  ``pos <= q_pos``, sliding window);
* per-slot ``kv_len`` bounding — blocks past the high-water mark are
  *skipped*, not just masked (verified by poisoning the tail);
* fused Int8KV dequant inside the tile — the decode path never
  materializes a float copy of the cache (verified by spying on
  ``dequant_kv``);
* a slot with no valid entries (kv_len == 0) returns exactly zeros.

Continuous serving with ``kernel_path="interpret"`` forced must stay
token-exact versus the same-path reference decode (float) and the
fake-quant float oracle (int8) — including the gemma3-style
local:global sliding-window ring architecture.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, flags
from repro.core import quantize as qz
from repro.kernels import ops
from repro.models import api
from repro.models.params import init_params
from repro.models.transformer import grow_cache
from repro.serve.server import PagedBatchServer


# ---------------------------------------------------------------------------
# Kernel parity: interpret-mode Pallas vs jnp ref
# ---------------------------------------------------------------------------
def _slot_case(rng, b, s, hq, hkv, d, kv_lens, pads):
    """Build a slot-cache decode case: row i holds ``kv_lens[i]`` entries
    (−1 positions beyond), the first ``pads[i]`` of them left-pad (−1)."""
    q = jnp.asarray(rng.randn(b, 1, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
    pos = np.full((b, s), -1, np.int32)
    for i, (n, pad) in enumerate(zip(kv_lens, pads)):
        pos[i, pad:n] = np.arange(n - pad)
    q_pos = jnp.asarray(np.maximum(np.array(kv_lens) - np.array(pads) - 1, 0),
                        jnp.int32)
    return q, k, v, q_pos, jnp.asarray(pos), jnp.asarray(kv_lens, jnp.int32)


@pytest.mark.parametrize("precision", ["float", "int8"])
@pytest.mark.parametrize("window", [0, 4])
@pytest.mark.parametrize("hkv", [4, 2, 1])     # GQA ratios 1, 2, 4
def test_flash_decode_parity(hkv, window, precision):
    """interpret == ref across GQA ratios, windows, precisions, and
    ragged per-slot kv_len including an empty slot."""
    rng = np.random.RandomState(0)
    b, s, hq, d = 4, 24, 4, 16
    q, k, v, q_pos, pos, kvl = _slot_case(
        rng, b, s, hq, hkv, d, kv_lens=[0, 3, s, 10], pads=[0, 1, 2, 3])
    if precision == "int8":
        k, v = qz.quant_kv(k), qz.quant_kv(v)
    out_ref = ops.decode_attention(q, k, v, q_pos, pos, window=window,
                                   kv_len=kvl, force="ref")
    out_int = ops.decode_attention(q, k, v, q_pos, pos, window=window,
                                   kv_len=kvl, force="interpret")
    np.testing.assert_allclose(np.asarray(out_int), np.asarray(out_ref),
                               atol=1e-5)
    # empty slot (kv_len == 0): exactly zero on both paths
    assert np.all(np.asarray(out_ref)[0] == 0)
    assert np.all(np.asarray(out_int)[0] == 0)


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_flash_decode_parity_unbounded(precision):
    """kv_len=None (no bound: plain masked decode) still matches."""
    rng = np.random.RandomState(1)
    b, s, hq, hkv, d = 2, 17, 4, 2, 8      # ragged S exercises padding
    q, k, v, q_pos, pos, _ = _slot_case(
        rng, b, s, hq, hkv, d, kv_lens=[5, s], pads=[0, 2])
    if precision == "int8":
        k, v = qz.quant_kv(k), qz.quant_kv(v)
    out_ref = ops.decode_attention(q, k, v, q_pos, pos, force="ref")
    out_int = ops.decode_attention(q, k, v, q_pos, pos, force="interpret")
    np.testing.assert_allclose(np.asarray(out_int), np.asarray(out_ref),
                               atol=1e-5)


def test_flash_decode_parity_ring_positions():
    """Sliding-window ring layout: positions wrap (slot = pos % w), the
    newest entries overwrite the oldest — masking is purely
    position-driven, so order in the cache must not matter."""
    rng = np.random.RandomState(2)
    b, w, hq, hkv, d = 2, 8, 4, 2, 16
    q = jnp.asarray(rng.randn(b, 1, hq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, w, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, w, hkv, d), jnp.float32)
    # row 0: wrapped ring at position 11 (slots hold pos 8..11, 4..7)
    # row 1: part-filled ring at position 2
    pos = np.array([[8, 9, 10, 11, 4, 5, 6, 7],
                    [0, 1, 2, -1, -1, -1, -1, -1]], np.int32)
    q_pos = jnp.asarray([11, 2], jnp.int32)
    kvl = jnp.asarray([w, 3], jnp.int32)
    out_ref = ops.decode_attention(q, k, v, q_pos, jnp.asarray(pos),
                                   window=w, kv_len=kvl, force="ref")
    out_int = ops.decode_attention(q, k, v, q_pos, jnp.asarray(pos),
                                   window=w, kv_len=kvl, force="interpret")
    np.testing.assert_allclose(np.asarray(out_int), np.asarray(out_ref),
                               atol=1e-5)


def test_kv_len_blocks_really_skipped():
    """Poison the cache beyond kv_len with valid-looking entries: the
    kernel must not read them (the bound is a skip, not a mask), and the
    ref applies the same index bound."""
    rng = np.random.RandomState(3)
    b, s, hq, hkv, d = 2, 32, 4, 2, 16
    q, k, v, q_pos, pos, kvl = _slot_case(
        rng, b, s, hq, hkv, d, kv_lens=[6, 9], pads=[0, 0])
    clean = [ops.decode_attention(q, k, v, q_pos, pos, kv_len=kvl,
                                  force=f) for f in ("ref", "interpret")]
    # poison: attendable positions + huge values in the dead tail
    pos_bad = np.asarray(pos).copy()
    k_bad, v_bad = np.asarray(k).copy(), np.asarray(v).copy()
    for i, n in enumerate(np.asarray(kvl)):
        pos_bad[i, n:] = 0                      # pos 0 <= q_pos: attendable
        k_bad[i, n:] = 100.0
        v_bad[i, n:] = 100.0
    for f, want in zip(("ref", "interpret"), clean):
        got = ops.decode_attention(q, jnp.asarray(k_bad), jnp.asarray(v_bad),
                                   q_pos, jnp.asarray(pos_bad), kv_len=kvl,
                                   force=f)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f)


# ---------------------------------------------------------------------------
# Serving through the kernel: token-exact with kernel_path=interpret
# ---------------------------------------------------------------------------
@pytest.fixture()
def interpret_path():
    old = flags.get("kernel_path")
    flags.set_flags(kernel_path="interpret")
    yield
    flags.set_flags(kernel_path=old)


def _setup(arch):
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype="float32")
    return cfg, init_params(cfg, jax.random.key(0))


def _reference_decode(cfg, params, prompt, max_new, policy=None):
    """Contiguous no-batching decode on whatever kernel path is pinned."""
    fns = api.model_fns(cfg)
    logits, cache = fns.forward_prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :])}, policy)
    cache = grow_cache(cfg, cache, max_new + 1)
    out = [int(jnp.argmax(logits, -1)[0])]
    pos = len(prompt)
    for _ in range(max_new - 1):
        logits, cache = fns.forward_decode(
            cfg, params, cache, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32), policy=policy)
        out.append(int(jnp.argmax(logits, -1)[0]))
        pos += 1
    return out


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-4b"])
def test_continuous_serving_interpret_float(arch, interpret_path):
    """Slot-recycled serving through the Pallas (interpret) decode kernel
    — per-slot kv_len bounding, chunked pad-free prefill, ring caches —
    token-exact vs an unpadded contiguous decode on the same path."""
    cfg, params = _setup(arch)
    rng = np.random.RandomState(5)
    lens, budgets = [3, 9, 6], [4, 3, 5]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                           prefill_chunk=4, max_new_tokens=8)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    srv.run()
    for r, p, bud in zip(reqs, prompts, budgets):
        assert r.tokens == _reference_decode(cfg, params, p, bud), \
            f"rid {r.rid}: kernel-path serving diverged from reference"


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-4b"])
def test_continuous_serving_interpret_int8_vs_fakequant(arch,
                                                        interpret_path):
    """ACCEPTANCE: native int8 serving with the decode kernel forced on
    == the fake-quant float oracle.  The oracle's float cache holds
    exactly the dequantized int8 values, so if the kernel's in-tile
    dequant is faithful (and nothing dequantizes the cache outside the
    tile) the two runs are bit-identical → token-exact."""
    cfg, params = _setup(arch)
    rng = np.random.RandomState(6)
    lens, budgets = [3, 8, 5], [4, 3, 5]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                           prefill_chunk=4, max_new_tokens=8,
                           precision="int8")
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    srv.run()
    oracle = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                              prefill_chunk=4, max_new_tokens=8,
                              precision="int8_fakequant")
    oreqs = oracle.submit(prompts, max_new_tokens=budgets)
    oracle.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in oreqs], \
        "native int8 decode kernel diverged from the fake-quant oracle"


def test_int8_decode_never_dequantizes_cache(monkeypatch):
    """The int8 decode path must not call ``dequant_kv`` at all — dequant
    happens only inside the kernel tile / per-tile ref scan.  (The
    fake-quant *oracle* legitimately round-trips the single new (B, 1)
    KV entry at write time; the native path doesn't even do that.)"""
    calls = []
    real = qz.dequant_kv

    def spy(kv, dtype=jnp.float32):
        calls.append(tuple(kv.q.shape))
        return real(kv, dtype)

    monkeypatch.setattr("repro.models.layers.dequant_kv", spy)
    cfg, params = _setup("internlm2-1.8b")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(2)]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=8,
                           max_new_tokens=4, precision="int8")
    srv.submit(prompts)
    srv.run()
    assert calls == [], \
        f"int8 decode materialized dequantized KV: shapes {calls}"
