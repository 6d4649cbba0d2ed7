"""Continuous-batching serving engine tests (paper §4.6), on
``PagedBatchServer``.

Covers the scheduler invariants the engine is built on: slot recycling
admits queued work before the batch drains, per-request budgets are
honored in-step, chunked pad-free prefill is token-exact versus an
unpadded no-batching reference decode, over-capacity prompts error
explicitly (never silently truncate), and metrics are sane.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import quantize as qz
from repro.models import api
from repro.models.params import init_params
from repro.models.transformer import grow_cache
from repro.serve.kvcache import (alloc_paged_cache, paged_slot_axes,
                                 put_slot, take_slot)
from repro.serve.scheduler import Slot
from repro.serve.server import PagedBatchServer, _chunk_rows

ARCH = "internlm2-1.8b"


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_smoke(ARCH)
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _reference_decode(cfg, params, prompt, max_new):
    """No-batching oracle: exact-length prefill + contiguous decode."""
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
# Scheduler units (host-side, no model)
# ---------------------------------------------------------------------------
def test_slot_lifecycle():
    """FREE → PREFILLING → ACTIVE → FREE, with the pad-free invariant
    write_idx == position == prompt_len at decode start."""
    s = Slot(0)
    assert s.free and not s.prefilling and not s.active
    s.occupy(rid=7, prompt=np.arange(11, dtype=np.int32), max_new=4)
    assert s.prefilling and not s.active and not s.free
    s.chunk_pos = 11
    s.begin_decode()
    assert s.active and not s.prefilling
    assert s.position == 11 and s.write_idx == 11 and s.generated == 1
    s.advance()
    assert s.position == 12 and s.write_idx == 12
    s.release()
    assert s.free and s.prompt is None


def test_slot_scheduler_fcfs(setup):
    """The engine's admission fills free slots in FCFS order, and a
    request with no free slot waits at the queue head until one frees."""
    cfg, params = setup
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=8,
                           max_new_tokens=4)
    a, b, c = srv.submit([np.arange(4, dtype=np.int32)] * 3)
    srv._admit(0)
    assert [s.rid for s in srv.sched.slots] == [a.rid, b.rid]
    assert [r.rid for r in srv.sched.waiting] == [c.rid]
    srv._admit(0)                    # no free slot for "c"
    assert [r.rid for r in srv.sched.waiting] == [c.rid]
    srv._free_slot(srv.sched.slots[0])
    srv._admit(0)
    assert srv.sched.slots[0].rid == c.rid and not srv.sched.waiting


def test_chunk_rows():
    assert _chunk_rows(8, 8) == 8
    assert _chunk_rows(9, 8) == 16
    assert _chunk_rows(1, 8) == 8
    assert _chunk_rows(16, 4) == 16


def test_over_capacity_prompt_errors(setup):
    """No silent truncation: a prompt that cannot fit a slot errors at
    submit (the old bucket policy kept the most recent tokens and
    silently dropped the rest)."""
    cfg, params = setup
    srv = PagedBatchServer(cfg, params, slots=1, max_prompt=16,
                           max_new_tokens=8)
    rng = np.random.RandomState(0)
    with pytest.raises(ValueError, match="cache rows"):
        srv.submit([rng.randint(0, cfg.vocab_size, 200).astype(np.int32)])
    with pytest.raises(ValueError, match="empty"):
        srv.submit([np.zeros((0,), np.int32)])
    # submit is atomic: a rejected batch registers nothing, even when
    # earlier prompts in it were fine
    ok = rng.randint(0, cfg.vocab_size, 5).astype(np.int32)
    bad = rng.randint(0, cfg.vocab_size, 200).astype(np.int32)
    with pytest.raises(ValueError):
        srv.submit([ok, bad])
    assert srv.requests == {} and not srv.sched.waiting


# ---------------------------------------------------------------------------
# Engine behavior
# ---------------------------------------------------------------------------
def test_slot_recycling_admits_before_drain(setup):
    """A queued request must be admitted into a freed slot while another
    request is still decoding — the continuous-batching invariant."""
    cfg, params = setup
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(3)]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=8,
                           prefill_chunk=8, max_new_tokens=12)
    # slot 0 finishes early (2 tokens), slot 1 runs long (12); request 3
    # must start before request 2 finishes.
    r1, r2, r3 = srv.submit(prompts, max_new_tokens=[2, 12, 6])
    srv.run()
    assert r1.finished_step is not None and r2.finished_step is not None
    assert r3.admitted_step is not None
    assert r3.admitted_step < r2.finished_step, \
        "queued request waited for the whole batch (static behavior)"
    # and it actually decoded to completion
    assert len(r3.tokens) == 6


def test_per_request_max_new_honored(setup):
    cfg, params = setup
    rng = np.random.RandomState(1)
    budgets = [1, 3, 7, 5]
    prompts = [rng.randint(0, cfg.vocab_size, 5).astype(np.int32)
               for _ in budgets]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=8,
                           max_new_tokens=8)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    m = srv.run()
    assert [len(r.tokens) for r in reqs] == budgets
    assert m["tokens_generated"] == sum(budgets)


def test_chunked_prefill_matches_reference(setup):
    """Chunked pad-free prefill + slot decode must be token-exact vs an
    unpadded single-request decode (no pad row ever enters the cache)."""
    cfg, params = setup
    rng = np.random.RandomState(2)
    lens = [3, 11, 7, 16]
    budgets = [5, 4, 6, 3]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                           prefill_chunk=4, max_new_tokens=8)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    srv.run()
    for r, p, b in zip(reqs, prompts, budgets):
        assert r.tokens == _reference_decode(cfg, params, p, b), \
            f"rid {r.rid}: chunked serve diverged from reference"


def test_static_and_continuous_agree(setup):
    """Scheduling must not change the tokens, only the latency: one slot
    serves the requests one at a time, two slots batch and recycle."""
    cfg, params = setup
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (4, 9, 12, 6)]
    budgets = [3, 6, 2, 5]
    one = PagedBatchServer(cfg, params, slots=1, max_prompt=16,
                           max_new_tokens=8)
    oreqs = one.submit(prompts, max_new_tokens=budgets)
    one.run()
    two = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                           max_new_tokens=8)
    treqs = two.submit(prompts, max_new_tokens=budgets)
    two.run()
    assert [r.tokens for r in oreqs] == [r.tokens for r in treqs]


def test_metrics_sanity(setup):
    cfg, params = setup
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, 6).astype(np.int32)
               for _ in range(4)]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=8,
                           max_new_tokens=4)
    reqs = srv.submit(prompts)
    m = srv.run()
    assert m["requests"] == 4
    assert m["tokens_per_s"] > 0
    assert m["tokens_generated"] == 16
    assert 0 < m["ttft_p50_s"] <= m["ttft_p95_s"]
    assert 0 < m["slot_utilization"] <= 1.0
    assert m["prefill_chunks"] >= 4       # ≥ one chunk per request
    # pad-free: the measured fill can never exceed what live tokens
    # occupy (pads used to inflate this)
    assert 0 < m["kv_fill_frac"] <= 1.0
    # TTFT ordering: requests admitted later can't have earlier first
    # tokens (FCFS admission, monotone clock)
    firsts = [r.first_token_at for r in reqs]
    assert firsts == sorted(firsts)


# the paged cache's slot view: 3 slots of 16 logical rows over a pool
# of 6 blocks of 8; slot 1's table row maps its first block to block 4
_VIEW = dict(slots=3, capacity=16, num_blocks=6, block_size=8)


def _arr(leaf):
    return np.asarray(leaf.q if isinstance(leaf, qz.Int8KV) else leaf)


def _chunk_into_slot1(cfg, params, policy=None):
    """One 8-token chunk run in slot 1's view of an empty paged cache
    and spliced back; returns (cache, cache after the splice)."""
    axes = paged_slot_axes(cfg, policy=policy, **_VIEW)
    cache = alloc_paged_cache(cfg, policy=policy, **_VIEW)
    small = take_slot(cache, axes, 1)
    for key in ("global_k", "pool_pos"):      # pool leaves: taken whole
        assert _arr(small[key]).shape == _arr(cache[key]).shape, key
    assert _arr(small["local_k"]).shape[2] == 1
    fns = api.model_fns(cfg)
    toks = jnp.asarray(np.arange(8, dtype=np.int32)[None, :])
    _, small2 = fns.forward_prefill_chunk(
        cfg, params, small, toks, toks, policy=policy,
        kv_len=jnp.asarray([8], jnp.int32),
        block_table=jnp.asarray([[4, 0]], jnp.int32))
    return cache, put_slot(cache, small2, axes, 1)


def _check_slot1_only(cache, cache2):
    """Slot leaves: rows 0 and 2 untouched, row 1 written.  Pool
    leaves: block 4 written, every other block untouched."""
    lp = np.asarray(cache2["local_pos"])
    assert np.all(lp[[0, 2]] == -1), "neighbor slots disturbed"
    assert sorted(lp[1]) == list(range(8))
    pp = np.asarray(cache2["pool_pos"])
    assert list(pp[4]) == list(range(8))
    assert np.all(np.delete(pp, 4, axis=0) == -1), "other blocks written"
    lk2, lk0 = _arr(cache2["local_k"]), _arr(cache["local_k"])
    assert np.array_equal(lk2[:, :, [0, 2]], lk0[:, :, [0, 2]])
    assert np.any(lk2[:, :, 1] != 0)
    gk2, gk0 = _arr(cache2["global_k"]), _arr(cache["global_k"])
    assert np.array_equal(np.delete(gk2, 4, axis=1),
                          np.delete(gk0, 4, axis=1))
    assert np.any(gk2[:, 4] != 0)


def test_slot_view_isolated(ring_setup):
    """take_slot/put_slot over the paged cache (``paged_slot_axes``)
    touch exactly one slot's slot-addressed leaves (the sliding-window
    ring) and pass the pool leaves through whole: a chunk lands in its
    slot's ring rows and in the pool block its table row names."""
    cfg, params = ring_setup
    cache, cache2 = _chunk_into_slot1(cfg, params)
    _check_slot1_only(cache, cache2)


def test_kv_cache_bytes_encdec_sizing():
    """Pin the enc-dec sizing formula: encoder layers hold NO decode
    cache (the encoder runs once; its output is the cross KV); the
    decoder holds self-attn KV over seq plus cross-attn KV over the
    subsampled encoder length.  Cross-checked against the leaf bytes of
    an actual prefill cache."""
    from repro.core.arch import ShapeConfig
    from repro.serve.kvcache import kv_cache_bytes

    cfg = configs.get("seamless-m4t-large-v2")
    b, s, db = 2, 1024, 2
    per_entry = 2 * b * cfg.n_kv_heads * cfg.resolved_head_dim * db
    expect = (cfg.n_layers * per_entry * s
              + cfg.n_layers * per_entry * (s // cfg.enc_seq_divisor))
    assert kv_cache_bytes(cfg, b, s, db) == expect

    # the abstract prefill cache's K/V leaves carry exactly those bytes
    smoke = configs.get_smoke("seamless-m4t-large-v2")
    b2, s2 = 2, 16
    cache = api.abstract_cache(
        smoke, ShapeConfig("sizing", seq_len=s2, global_batch=b2,
                           kind="prefill"))
    kv_bytes = sum(
        int(np.prod(cache[key].shape))
        * jnp.dtype(cache[key].dtype).itemsize
        for key in ("k", "v", "xk", "xv"))
    itemsize = jnp.dtype(cache["k"].dtype).itemsize
    assert kv_bytes == kv_cache_bytes(smoke, b2, s2, itemsize)


# ---------------------------------------------------------------------------
# Slot lifecycle: reset → chunked prefill → release → re-admit, float + int8
# ---------------------------------------------------------------------------
def test_slot_view_isolated_int8(ring_setup):
    """The int8 paged cache (Int8KV pairs) honors the same slot-view
    contract: one slot's ring rows and one pool block written through a
    chunk, neighbors untouched, scales written with their values."""
    cfg, params = ring_setup
    qparams = qz.quantize_model_params(params, qz.INT8)
    cache, cache2 = _chunk_into_slot1(cfg, qparams, qz.INT8)
    assert isinstance(cache2["local_k"], qz.Int8KV)
    assert isinstance(cache2["global_k"], qz.Int8KV)
    _check_slot1_only(cache, cache2)
    ls = np.asarray(cache2["local_k"].scale)
    gs = np.asarray(cache2["global_k"].scale)
    assert np.all(ls[:, :, 1] > 0), "ring scales not written with values"
    assert np.all(gs[:, 4] > 0), "pool scales not written with values"


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_slot_reuse_after_release_exact(setup, precision):
    """A slot that went reset → chunked prefill → release must serve its
    next request exactly: stale KV from the previous occupant (blocks
    are handed on unscrubbed, fenced only by kv_len) can never leak into
    attention."""
    cfg, params = setup
    if precision == "int8":
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 9, 4)]
    budgets = [4, 3, 5]
    # one slot and a one-slot pool: every request reuses the same slot
    # and the same physical blocks sequentially, with no scrub between
    srv = PagedBatchServer(cfg, params, slots=1, max_prompt=16,
                           prefill_chunk=4, max_new_tokens=8,
                           precision=precision)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    srv.run()
    if precision == "float":
        refs = [_reference_decode(cfg, params, p, b)
                for p, b in zip(prompts, budgets)]
    else:
        # fresh single-request int8 servers: no prior slot occupancy
        refs = []
        for p, b in zip(prompts, budgets):
            one = PagedBatchServer(cfg, params, slots=1, max_prompt=16,
                                   prefill_chunk=4, max_new_tokens=8,
                                   precision="int8")
            (r,) = one.submit([p], max_new_tokens=[b])
            one.run()
            refs.append(r.tokens)
    assert [r.tokens for r in reqs] == refs, \
        "slot reuse leaked state between requests"


# ---------------------------------------------------------------------------
# Sliding-window ring caches (local_global arch), float + int8
# ---------------------------------------------------------------------------
RING_ARCH = "gemma3-4b"


@pytest.fixture(scope="module")
def ring_setup():
    import dataclasses
    cfg = dataclasses.replace(configs.get_smoke(RING_ARCH), dtype="float32")
    params = init_params(cfg, jax.random.key(1))
    return cfg, params


def test_ring_prefill_quantizes_after_gather(ring_setup):
    """Int8 ring caches are the quantization of the float ring caches:
    per-entry quantization commutes with ``_ring_select``'s gather, so
    one code path covers contiguous and ring layouts."""
    cfg, params = ring_setup
    fns = api.model_fns(cfg)
    toks = jnp.asarray(np.arange(16, dtype=np.int32)[None, :])
    _, float_cache = fns.forward_prefill(cfg, params, {"tokens": toks})
    _, q_cache = fns.forward_prefill(cfg, params, {"tokens": toks}, qz.INT8)
    for key in ("local_k", "local_v", "tail_k", "global_k"):
        if key not in float_cache:
            continue
        expect = qz.quant_kv(float_cache[key])
        got = q_cache[key]
        assert isinstance(got, qz.Int8KV), key
        np.testing.assert_array_equal(np.asarray(got.q),
                                      np.asarray(expect.q), err_msg=key)
        np.testing.assert_array_equal(np.asarray(got.scale),
                                      np.asarray(expect.scale), err_msg=key)
    np.testing.assert_array_equal(np.asarray(q_cache["local_pos"]),
                                  np.asarray(float_cache["local_pos"]))


@pytest.mark.parametrize("precision", ["float", "int8"])
def test_ring_serving_token_exact(ring_setup, precision):
    """Serving on a local:global sliding-window arch — ring caches
    filled by chunked scatter writes, ring-slot decode writes —
    is token-exact vs the contiguous reference (float) or the fake-quant
    float simulation (int8)."""
    cfg, params = ring_setup
    rng = np.random.RandomState(8)
    lens = [5, 12, 9]
    budgets = [4, 6, 3]
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    srv = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                           prefill_chunk=8, max_new_tokens=8,
                           precision=precision)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    srv.run()
    if precision == "float":
        refs = [_reference_decode(cfg, params, p, b)
                for p, b in zip(prompts, budgets)]
    else:
        fq = PagedBatchServer(cfg, params, slots=2, max_prompt=16,
                              prefill_chunk=8, max_new_tokens=8,
                              precision="int8_fakequant")
        fq.submit(prompts, max_new_tokens=budgets)
        fq.run()
        refs = [r.tokens for r in fq.requests.values()]
    assert [r.tokens for r in reqs] == refs
