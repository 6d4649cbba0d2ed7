"""Paged KV cache: block-table memory manager end to end (docs/paged_kv.md).

The contracts under test:

* ``kv_block_size`` is the single source of block granularity (server
  capacity rounding == kernel tile == pool block).
* ``BlockManager``: free-list alloc/free with refcounts, exhaustion,
  hash-chain prefix caching (match capped at prompt − 1, registry holds
  its own reference, LRU reclaim under pressure).
* Kernel parity *through the block table*: interpret-mode Pallas
  ``flash_decode``/``flash_chunk_prefill`` against the ref oracle that
  gathers through the same table — scrambled physical placements,
  ragged ``kv_len``, empty slots, Int8KV, and the first and last layer
  of a stacked pool read in place — so the paged addressing itself is
  pinned, not just the softmax math.
* Paged continuous serving is token-exact vs the unpadded one-shot
  reference on {uniform, ring, ssm, hybrid} × {float, int8} (the uniform
  family also through the Pallas kernels in interpret mode), including
  forced preempt-and-recompute and physical prefix sharing (asserted by
  pool accounting: live blocks < Σ per-request blocks).
* Slot/block recycling under churn — release → re-admit → preemption →
  re-prefill — is token-identical, including the gemma3 sliding-window
  ring (freed blocks reusable immediately).
* The paged AOT artifact carries ``block_table`` in its signature and
  pool pricing in its resource report.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, flags
from repro.kernels import flash_decode as fd
from repro.kernels import ref
from repro.models import api
from repro.models.params import init_params
from repro.models.transformer import grow_cache
from repro.serve.kvcache import (BlockManager, PoolExhausted,
                                 abstract_paged_cache, kv_block_size,
                                 kv_pool_block_bytes, paged_cache_keys)
from repro.serve.server import ContinuousBatchServer, PagedBatchServer


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype="float32")
    return cfg, init_params(cfg, jax.random.key(0))


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


# ---------------------------------------------------------------------------
# kv_block_size: one helper, three consumers
# ---------------------------------------------------------------------------
def test_kv_block_size_is_shared():
    """The dedupe contract: the server's effective KV block equals the
    helper (which equals the kernels' tile choice) at every capacity."""
    for cap, want in ((64, 64), (128, 128), (192, 64), (320, 64),
                      (72, 72), (144, 16), (8, 8)):
        assert kv_block_size(cap) == want, cap
    cfg, params = _setup("internlm2-1.8b")
    srv = ContinuousBatchServer(cfg, params, slots=1, max_prompt=16,
                                max_new_tokens=4)
    assert srv._kv_block == kv_block_size(srv.capacity)
    psrv = PagedBatchServer(cfg, params, slots=1, max_prompt=16,
                            max_new_tokens=4)
    assert psrv.block_size == kv_block_size(psrv.capacity)
    # per-block pricing honors a block_size override (a 256-row block
    # costs exactly 2x a 128-row block — the inner abstract pool must
    # not silently re-derive kv_block_size(256) == 128)
    assert kv_pool_block_bytes(cfg, 256, None, 256) \
        == 2 * kv_pool_block_bytes(cfg, 256, None, 128)


# ---------------------------------------------------------------------------
# BlockManager (host-side, no model)
# ---------------------------------------------------------------------------
def test_block_manager_alloc_free_refcount():
    m = BlockManager(4, 8, prefix_cache=False)
    a = m.alloc(3)
    assert len(set(a)) == 3 and m.free_blocks == 1 and m.live_blocks == 3
    m.free(a[:1])
    assert m.free_blocks == 2
    b = m.alloc(2)
    assert m.free_blocks == 0
    with pytest.raises(PoolExhausted):
        m.alloc(1)
    m.free(a[1:])
    m.free(b)
    assert m.free_blocks == 4 and m.live_blocks == 0
    with pytest.raises(AssertionError):
        m.free(b[:1])                      # double free


def test_block_manager_prefix_cache():
    m = BlockManager(8, 4)
    toks = np.arange(13, dtype=np.int32)   # 3 full blocks + 1 spare token
    blocks = m.alloc(4)
    m.register_prefix(toks, blocks)        # registers blocks 0..2 (3 full)
    assert m.live_blocks == 8 - m.free_blocks
    m.free(blocks)                         # writer releases; cache holds 3
    assert m.free_blocks == 5
    # identical prompt: match capped at len-1 => (13-1)//4 = 3 full blocks
    hit = m.match_prefix(toks)
    assert hit == blocks[:3]
    # exactly block-aligned prompt of 12: cap (12-1)//4 = 2 blocks — the
    # last block must be recomputed to produce logits
    assert m.match_prefix(toks[:12]) == blocks[:2]
    m.free(hit)
    m.free(blocks[:2])
    # diverging prompt: only the shared leading blocks match
    other = toks.copy()
    other[5] = 999
    assert m.match_prefix(other) == blocks[:1]
    m.free(blocks[:1])
    # pool pressure reclaims cached-but-unreferenced blocks (LRU)
    taken = m.alloc(8)
    assert m.free_blocks == 0 and m.stats["reclaimed"] == 3
    assert m.match_prefix(toks) == []      # registry emptied by reclaim
    m.free(taken)


# ---------------------------------------------------------------------------
# Kernel parity through the block table (interpret vs gather-ref)
# ---------------------------------------------------------------------------
def _paged_case(rng, b, n_tbl, nb, bs, hkv, d, fills, *, int8=False):
    """Scrambled physical placement: slot rows map to a shuffled set of
    pool blocks; pool entries outside any live region keep poisoned
    positions/values (they must never be read thanks to kv_len)."""
    kp = rng.randn(nb, bs, hkv, d).astype(np.float32)
    vp = rng.randn(nb, bs, hkv, d).astype(np.float32)
    pos = rng.randint(0, 3, (nb, bs)).astype(np.int32)   # poison
    table = np.zeros((b, n_tbl), np.int32)
    order = rng.permutation(nb)
    nxt = 0
    for i, fill in enumerate(fills):
        for j in range(-(-fill // bs) if fill else 0):
            blk = int(order[nxt]); nxt += 1
            table[i, j] = blk
            n = min(bs, fill - j * bs)
            pos[blk, :n] = np.arange(j * bs, j * bs + n)
            pos[blk, n:] = -1
    out = dict(k=jnp.asarray(kp), v=jnp.asarray(vp),
               pos=jnp.asarray(pos), table=jnp.asarray(table),
               kvl=jnp.asarray(fills, jnp.int32))
    if int8:
        out["ks"] = jnp.asarray(
            rng.uniform(0.01, 0.1, (nb, bs, hkv)).astype(np.float32))
        out["vs"] = jnp.asarray(
            rng.uniform(0.01, 0.1, (nb, bs, hkv)).astype(np.float32))
        out["k"] = jnp.asarray(rng.randint(-127, 128, kp.shape), jnp.int8)
        out["v"] = jnp.asarray(rng.randint(-127, 128, vp.shape), jnp.int8)
    return out


_N_LAYERS = 3
# None: one layer's pool; 0 and L − 1: that layer of a stacked pool
_LAYERS = pytest.mark.parametrize("layer", [None, 0, _N_LAYERS - 1],
                                  ids=["pool", "layer0", "layer_last"])


def _pool_operands(rng, c, layer):
    """Kernel operands (k, v, scales, layer) for ``c``'s pool: as is, or
    stacked as layer ``layer`` of ``_N_LAYERS`` whose other layers hold
    other values, so a read of the wrong layer cannot pass."""
    names = ("k", "v", "ks", "vs")
    if layer is None:
        leaves = [c.get(n) for n in names]
    else:
        leaves = []
        for n in names:
            x = c.get(n)
            if x is not None:
                other = rng.randn(_N_LAYERS, *x.shape) * 3
                x = jnp.asarray(other, x.dtype).at[layer].set(x)
            leaves.append(x)
        layer = jnp.int32(layer)
    k, v, ks, vs = leaves
    return k, v, dict(k_scale=ks, v_scale=vs, layer=layer)


@_LAYERS
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("g", [1, 2])
def test_paged_flash_decode_parity(int8, g, layer):
    rng = np.random.RandomState(0)
    b, hkv, d, bs, n_tbl, nb = 4, 2, 16, 8, 4, 9
    fills = np.array([5, 0, 32, 17], np.int32)   # ragged + empty + full
    c = _paged_case(rng, b, n_tbl, nb, bs, hkv, d, fills, int8=int8)
    q = rng.randn(b, hkv, g, d).astype(np.float32)
    qp = jnp.asarray(np.maximum(fills - 1, 0), jnp.int32)
    scales = dict(k_scale=c.get("ks"), v_scale=c.get("vs"))
    k, v, kw = _pool_operands(rng, c, layer)
    got = fd.flash_decode(jnp.asarray(q), k, v, qp, c["pos"], c["kvl"],
                          block_table=c["table"], interpret=True, **kw)
    q_ref = q.reshape(b, hkv * g, d)[:, None]
    want = ref.paged_decode_attention_ref(
        jnp.asarray(q_ref), c["k"], c["v"], qp, c["pos"], c["table"],
        c["kvl"], **scales)
    np.testing.assert_allclose(np.asarray(got).reshape(b, hkv * g, d),
                               np.asarray(want)[:, 0], atol=2e-5)
    assert np.abs(np.asarray(got)[1]).max() == 0.0   # empty slot → zeros


@_LAYERS
@pytest.mark.parametrize("int8", [False, True])
def test_paged_chunk_prefill_parity(int8, layer):
    rng = np.random.RandomState(1)
    b, hkv, g, cq, d, bs, n_tbl, nb = 3, 2, 2, 4, 16, 8, 4, 8
    fills = np.array([8, 20, 12], np.int32)      # post-write fills p + C
    c = _paged_case(rng, b, n_tbl, nb, bs, hkv, d, fills, int8=int8)
    # chunk queries at the tail of each fill; one ragged row (2 pads)
    qpos = np.full((b, cq), -1, np.int32)
    reals = (4, 4, 2)
    for i, (f, r) in enumerate(zip(fills, reals)):
        qpos[i, :r] = np.arange(f - r, f)
    q = rng.randn(b, hkv, cq * g, d).astype(np.float32)
    qp_rows = np.repeat(qpos, g, axis=1)         # (B, C·G), (query, group)
    scales = dict(k_scale=c.get("ks"), v_scale=c.get("vs"))
    k, v, kw = _pool_operands(rng, c, layer)
    got = fd.flash_chunk_prefill(
        jnp.asarray(q), k, v, jnp.asarray(qp_rows), c["pos"], c["kvl"],
        block_table=c["table"], interpret=True, **kw)
    q_ref = q.reshape(b, hkv, cq, g, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, cq, hkv * g, d)
    want = ref.paged_chunk_attention_ref(
        jnp.asarray(q_ref), c["k"], c["v"], jnp.asarray(qpos), c["pos"],
        c["table"], c["kvl"], **scales)
    want = np.asarray(want).reshape(b, cq, hkv, g, d) \
        .transpose(0, 2, 1, 3, 4).reshape(b, hkv, cq * g, d)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # pad query rows (grouped rows c·G + g with c >= reals) → exact zeros
    assert np.abs(np.asarray(got)[2][:, 2 * g:, :]).max() == 0.0


# ---------------------------------------------------------------------------
# Serving: token-exact on every family × precision (ACCEPTANCE)
# ---------------------------------------------------------------------------
_LENS, _BUDGETS = (5, 12, 9, 3, 16), (6, 4, 8, 5, 3)


def _workload(cfg, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
            for n in _LENS]


_PAGED_KW = dict(slots=2, max_prompt=16, prefill_chunk=4,
                 max_new_tokens=8, block_size=8)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-4b",
                                  "falcon-mamba-7b", "zamba2-2.7b"])
def test_paged_serving_token_exact_float(arch):
    """ACCEPTANCE: paged continuous serving — block tables, multi-block
    slots, slot recycling — is token-exact vs the unpadded one-shot
    reference on uniform, ring, SSM, and hybrid families."""
    cfg, params = _setup(arch)
    prompts = _workload(cfg)
    srv = PagedBatchServer(cfg, params, **_PAGED_KW)
    reqs = srv.submit(prompts, max_new_tokens=list(_BUDGETS))
    srv.run()
    for r, p, b in zip(reqs, prompts, _BUDGETS):
        assert r.tokens == _reference_decode(cfg, params, p, b), \
            f"{arch} rid {r.rid} diverged"


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-4b",
                                  "falcon-mamba-7b", "zamba2-2.7b"])
def test_paged_serving_token_exact_int8(arch):
    """ACCEPTANCE: native int8 paged serving == the fake-quant float
    oracle through the same paged schedule on every family."""
    cfg, params = _setup(arch)
    prompts = _workload(cfg, seed=6)
    srv = PagedBatchServer(cfg, params, precision="int8", **_PAGED_KW)
    reqs = srv.submit(prompts, max_new_tokens=list(_BUDGETS))
    srv.run()
    fq = PagedBatchServer(cfg, params, precision="int8_fakequant",
                          **_PAGED_KW)
    freqs = fq.submit(prompts, max_new_tokens=list(_BUDGETS))
    fq.run()
    assert [r.tokens for r in reqs] == [r.tokens for r in freqs], \
        f"{arch}: int8 diverged from fake-quant oracle"


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_paged_serving_interpret_token_exact(precision, monkeypatch):
    """Paged serving through the Pallas kernels (interpret mode): the
    layer scan carries the stacked pool and each kernel call reads its
    layer in place.  Float == the unpadded reference on the same kernel
    path; int8 == the fake-quant oracle through the same schedule."""
    monkeypatch.setitem(flags.FLAGS, "kernel_path", "interpret")
    cfg, params = _setup("internlm2-1.8b")
    prompts = _workload(cfg)[:3]
    budgets = list(_BUDGETS[:3])
    srv = PagedBatchServer(cfg, params, precision=precision, **_PAGED_KW)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    srv.run()
    if precision == "float":
        refs = [_reference_decode(cfg, params, p, b)
                for p, b in zip(prompts, budgets)]
    else:
        fq = PagedBatchServer(cfg, params, precision="int8_fakequant",
                              **_PAGED_KW)
        fq.submit(prompts, max_new_tokens=budgets)
        fq.run()
        refs = [r.tokens for r in fq.requests.values()]
    assert [r.tokens for r in reqs] == refs, \
        f"{precision}: paged serving through the kernels diverged"


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_paged_forced_preemption_token_exact(precision):
    """ACCEPTANCE: a pool too small for the workload forces at least one
    preempt-and-recompute, and the token streams still match the
    reference (float) / fake-quant oracle (int8) exactly."""
    cfg, params = _setup("internlm2-1.8b")
    rng = np.random.RandomState(5)
    lens, budgets = [14, 15, 13], [12, 12, 12]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    kw = dict(slots=3, max_prompt=16, prefill_chunk=4, max_new_tokens=12,
              block_size=8, pool_blocks=8, prefix_cache=False)
    srv = PagedBatchServer(cfg, params, precision=precision, **kw)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    m = srv.run()
    assert m["preemptions"] > 0, "pool never ran dry — test is vacuous"
    if precision == "float":
        refs = [_reference_decode(cfg, params, p, b)
                for p, b in zip(prompts, budgets)]
    else:
        fq = PagedBatchServer(cfg, params, precision="int8_fakequant",
                              **kw)
        fq.submit(prompts, max_new_tokens=budgets)
        mf = fq.run()
        assert mf["preemptions"] > 0
        refs = [r.tokens for r in fq.requests.values()]
    assert [r.tokens for r in reqs] == refs, \
        "preempt-and-recompute diverged"


def test_paged_prefix_sharing_physical_and_exact():
    """ACCEPTANCE: two live requests sharing a prompt prefix physically
    share pool blocks — live blocks strictly below the sum of
    per-request block needs — and both streams match the reference."""
    cfg, params = _setup("internlm2-1.8b")
    rng = np.random.RandomState(9)
    base = rng.randint(0, cfg.vocab_size, 16).astype(np.int32)
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=24,
                           prefill_chunk=8, max_new_tokens=6,
                           block_size=8)
    # warm the prefix cache: one request over the shared prefix
    a, = srv.submit([base], max_new_tokens=[4])
    srv.run()
    assert a.tokens == _reference_decode(cfg, params, base, 4)
    # two concurrent requests extending the same prefix
    pb = np.concatenate([base, rng.randint(0, cfg.vocab_size, 4)
                         .astype(np.int32)])
    pc = np.concatenate([base, rng.randint(0, cfg.vocab_size, 2)
                         .astype(np.int32)])
    rb, rc = srv.submit([pb, pc], max_new_tokens=[5, 5])
    m = srv.run()
    assert m["prefix_hit_blocks"] > 0
    assert rb.tokens == _reference_decode(cfg, params, pb, 5)
    assert rc.tokens == _reference_decode(cfg, params, pc, 5)
    # pool accounting: while B and C were both live, the shared blocks
    # were counted once — peak live < what two private copies would need
    bs = srv.block_size
    private = sum(-(-(len(p) + 5) // bs) for p in (pb, pc))
    assert m["pool_live_blocks_peak"] < private + 0, \
        (m["pool_live_blocks_peak"], private)


@pytest.mark.parametrize("arch,precision", [
    ("internlm2-1.8b", "float"), ("internlm2-1.8b", "int8"),
    ("gemma3-4b", "float"), ("gemma3-4b", "int8"),
])
def test_paged_churn_recycling(arch, precision):
    """Slot/block recycling under churn: release → re-admit → forced
    preemption → re-prefill on ONE server instance stays token-identical
    across consecutive runs — including the gemma3 sliding-window ring
    (blocks freed on release/preemption are reused immediately by the
    next tenant with no scrub)."""
    cfg, params = _setup(arch)
    rng = np.random.RandomState(11)
    kw = dict(slots=2, max_prompt=16, prefill_chunk=4, max_new_tokens=12,
              block_size=8, pool_blocks=6, prefix_cache=False)
    srv = PagedBatchServer(cfg, params, precision=precision, **kw)
    oracle = (PagedBatchServer(cfg, params, precision="int8_fakequant",
                               **kw) if precision == "int8" else None)
    total_preempt = 0
    for wave in range(3):                 # three waves over the same pool
        lens = [14, 15, 13]
        budgets = [12, 11, 12]
        prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
                   for n in lens]
        reqs = srv.submit(prompts, max_new_tokens=budgets)
        m = srv.run()
        total_preempt += m["preemptions"]
        if oracle is None:
            refs = [_reference_decode(cfg, params, p, b)
                    for p, b in zip(prompts, budgets)]
        else:
            oreqs = oracle.submit(prompts, max_new_tokens=budgets)
            oracle.run()
            refs = [r.tokens for r in oreqs]
        assert [r.tokens for r in reqs] == refs, \
            f"{arch}/{precision} wave {wave} diverged"
        # every wave drains: all blocks return to the pool
        assert srv.manager.free_blocks == srv.pool_blocks
    assert total_preempt > 0, "churn never forced a preemption"


# ---------------------------------------------------------------------------
# AOT artifact + layout plumbing
# ---------------------------------------------------------------------------
def test_paged_artifact_signature_and_report():
    """The paged decode artifact takes (params, cache, token, position,
    kv_len, block_table) and prices the pool per block."""
    cfg, params = _setup("internlm2-1.8b")
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                           prefill_chunk=4, max_new_tokens=4,
                           block_size=8, use_artifact=True)
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9)]
    reqs = srv.submit(prompts, max_new_tokens=[3, 4])
    m = srv.run()
    assert m["artifact_bytes"] > 0
    mem = srv.artifact.memory
    assert mem["kv_pool_blocks"] == srv.pool_blocks
    assert mem["kv_block_bytes"] == kv_pool_block_bytes(
        cfg, srv.capacity, srv.prec, srv.block_size)
    for r, p, b in zip(reqs, prompts, (3, 4)):
        assert r.tokens == _reference_decode(cfg, params, p, b)


def test_paged_cache_layout_per_family():
    """Pool leaves replace exactly the full-attention rectangles; ring /
    SSM leaves keep their slot shapes; pure-SSM pages nothing."""
    for arch, keys in (("internlm2-1.8b", ("k", "v")),
                       ("gemma3-4b", ("global_k", "global_v")),
                       ("zamba2-2.7b", ("attn_k", "attn_v")),
                       ("falcon-mamba-7b", ())):
        cfg, _ = _setup(arch)
        assert paged_cache_keys(cfg) == keys, arch
        cache = abstract_paged_cache(cfg, slots=2, capacity=64,
                                     num_blocks=5, block_size=8)
        for k in keys:
            leaf = cache[k]
            arr = leaf.q if hasattr(leaf, "q") else leaf
            assert arr.shape[-4:-2] == (5, 8), (arch, k, arr.shape)
        if keys:
            assert cache["pool_pos"].shape == (5, 8)
            assert "full_pos" not in cache
        if arch == "gemma3-4b":
            # ring leaves stay slot-addressed at the window length
            assert cache["local_k"].shape[-4] == 2
            assert cache["local_pos"].shape[0] == 2
