"""Chip smoke run: the paged serving path end to end on one TPU chip.

    python chip_smoke.py

drives ``PagedBatchServer`` — chunked prefill, paged decode, Pallas
kernels — at the published widths of ``internlm2-1.8b`` (24 layers,
d_model 2048, 16 query / 8 kv heads, vocab 92544) with seeded random
weights and a seeded workload: 16 requests, prompts of 64–960 tokens,
64 new tokens each, 8 slots, 256-token prefill chunks (slot capacity
1024 = 8 pool blocks of 128 = 4 chunks).  Phases:

  (a) float server: every request gets its full 64 tokens, and the
      server's compiled chunk and decode programs contain Pallas
      kernels (``tpu_custom_call``);
  (b) the same server decoding through the AOT artifact
      (``use_artifact=True``) emits the same tokens;
  (c) one chunk-prefill step and one decode step run through the Pallas
      kernels and through the jnp references on identical inputs; the
      largest logit difference must stay within ``LOGIT_TOL`` (per
      precision) of the reference's largest logit;
  (d) (a) and (c) again at ``precision="int8"``, after the float server
      is freed.

It runs in this one process and starts no other.  It exits non-zero
without a result when JAX sees no TPU, when ``REPRO_KERNEL_PATH`` pins
anything but ``pallas``, or when any phase fails.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.  Timings and memory it
prints are smoke readings, not measurements.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
from repro import configs, flags
from repro.core.quantize import policy_for, serving_params
from repro.kernels import ops
from repro.launch import compile_cache
from repro.models.params import init_params, param_count
from repro.serve.kvcache import (alloc_paged_cache, kv_block_size,
                                 paged_slot_axes)
from repro.serve.serve_step import (make_paged_chunk_prefill_step,
                                    make_paged_decode_step)
from repro.serve.server import PagedBatchServer

ARCH = "internlm2-1.8b"
SEED = 0
N_REQUESTS = 16
PROMPT_LENS = (64, 960)
MAX_NEW = 64
SLOTS = 8
CHUNK = 256
# Pallas vs jnp reference on the same inputs, as a fraction of the
# reference's largest |logit|.  The two paths differ only inside the
# attention kernel (f32 in-tile arithmetic vs the reference's bf16
# probabilities and dequantized KV), so float logits drift by a few bf16
# ulps.  At int8 every projection re-quantizes its input per row, and a
# bf16-sized change flips some of those roundings by a whole int8 step,
# so the gap grows with depth: on a TPU v5e at full depth it was 3.2%
# (float) and 15.0% (int8).  Each limit sits between that and what
# broken kernels give.  At these widths on the CPU, interpret mode, depth
# cut to 2 / 6 layers, the sound gap was 1.1% / 1.8% (float) and 5.4% /
# 9.5% (int8); every head reading kv head 0 gave 136-151% at both depths
# and precisions (relative RMS 1.41: logits uncorrelated with the
# reference, so more depth cannot shrink it), and pointing the kernels
# at the wrong pool blocks 93-141%.  A bf16 output accumulator in the
# kernel gave 1.3% / 5.5%, inside the sound gap: this check cannot see
# accumulation precision, only faults that move attention to wrong keys.
LOGIT_TOL = {"float": 2.0 ** -4, "int8": 2.0 ** -2}


def workload(vocab: int):
    rng = np.random.RandomState(SEED)
    lens = rng.randint(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.randint(0, vocab, n).astype(np.int32) for n in lens]


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def server_programs_use_kernels(srv) -> dict:
    """Lower the server's own jitted chunk and decode steps at its live
    operand shapes and report whether each compiled program holds a
    Pallas kernel."""
    c, n = srv.chunk, srv.n_slots
    chunk = srv._chunk_step.lower(
        srv.params, srv.cache, jnp.zeros((1, c), jnp.int32),
        jnp.zeros((1, c), jnp.int32), 0, jnp.asarray([c], jnp.int32),
        jnp.asarray(srv.block_table[:1])).compile()
    vec = np.zeros((n,), np.int32)
    decode = srv.decode.lower(srv.params, srv.cache, vec, vec, vec,
                              jnp.asarray(srv.block_table)).compile()
    return {"chunk": _has_kernel(chunk), "decode": _has_kernel(decode)}


def serve(cfg, params, prompts, *, precision: str, use_artifact: bool):
    """Phase (a)/(b): serve ``prompts`` on a fresh paged server; returns
    (tokens per request, metrics).  The server is freed on return."""
    t0 = time.perf_counter()
    srv = PagedBatchServer(cfg, params, slots=SLOTS,
                           max_prompt=PROMPT_LENS[1],
                           prefill_chunk=CHUNK, max_new_tokens=MAX_NEW,
                           use_artifact=use_artifact, precision=precision)
    srv.submit(prompts)
    metrics = srv.run()
    metrics["first_run_s"] = time.perf_counter() - t0
    metrics["capacity"] = srv.capacity
    tokens = [srv.requests[i].tokens for i in sorted(srv.requests)]
    short = [i for i, t in enumerate(tokens) if len(t) != MAX_NEW]
    if short:
        raise AssertionError(f"requests {short} did not get {MAX_NEW} "
                             f"tokens ({precision}, artifact={use_artifact})")
    found = server_programs_use_kernels(srv)
    if not all(found.values()):
        raise AssertionError(f"no Pallas kernel in the server's "
                             f"compiled programs: {found}")
    metrics["tpu_custom_call"] = found
    return tokens, metrics


def compare_paths(cfg, params, *, precision: str, capacity: int) -> dict:
    """Phase (c): one chunk-prefill step (slot 0, a full chunk) and one
    decode step (every slot live, ragged fills, scrambled block table)
    through the Pallas kernels and through the jnp references, on
    identical inputs.  Returns the max |logit difference| of each step
    and the reference's max |logit|."""
    prec = policy_for(precision)
    params = serving_params(params, prec, cfg.activation_dtype)  # as held
    block = kv_block_size(capacity)
    n_table = capacity // block
    pool = SLOTS * n_table
    rng = np.random.RandomState(SEED + 1)
    table = rng.permutation(pool).astype(np.int32).reshape(SLOTS, n_table)
    axes = paged_slot_axes(cfg, SLOTS, capacity, pool, prec, block)
    cache = alloc_paged_cache(cfg, SLOTS, capacity, pool, prec, block)

    def chunk_args(cache, slot, r):
        toks = np.zeros((1, CHUNK), np.int32)
        poss = np.full((1, CHUNK), -1, np.int32)
        toks[0, :r] = rng.randint(0, cfg.vocab_size, r)
        poss[0, :r] = np.arange(r)
        return (params, cache, jnp.asarray(toks), jnp.asarray(poss), slot,
                jnp.asarray([CHUNK], jnp.int32),
                jnp.asarray(table[slot:slot + 1]))

    def build(path, make, args):
        saved = flags.get("kernel_path")
        flags.set_flags(kernel_path=path)
        try:
            return jax.jit(make(cfg, policy=prec)).lower(*args).compile()
        finally:
            flags.set_flags(kernel_path=saved)

    # the chunk step under test: slot 0, one full chunk
    args = chunk_args(cache, 0, CHUNK)
    make_chunk = (lambda cfg, policy: make_paged_chunk_prefill_step(
        cfg, axes=axes, policy=policy))
    k_chunk = build("pallas", make_chunk, args)
    r_chunk = build("ref", make_chunk, args)
    if not _has_kernel(k_chunk):
        raise AssertionError("Pallas chunk step holds no tpu_custom_call")
    if _has_kernel(r_chunk):
        raise AssertionError("reference chunk step holds a Pallas kernel")
    k_tok, k_logits, cache = k_chunk(*args)
    _, r_logits, _ = r_chunk(*args)
    out = _gap("chunk", k_logits, r_logits)

    # fill the other slots with ragged single chunks, then decode all
    lens = [CHUNK] + [int(n) for n in rng.randint(1, CHUNK + 1, SLOTS - 1)]
    last = [int(np.asarray(k_tok)[0, CHUNK - 1])]
    for s in range(1, SLOTS):
        tok, _, cache = k_chunk(*chunk_args(cache, s, lens[s]))
        last.append(int(np.asarray(tok)[0, lens[s] - 1]))
    pos = np.asarray(lens, np.int32)
    dargs = (params, cache, np.asarray(last, np.int32), pos, pos + 1,
             jnp.asarray(table))
    k_dec = build("pallas", make_paged_decode_step, dargs)
    r_dec = build("ref", make_paged_decode_step, dargs)
    if not _has_kernel(k_dec):
        raise AssertionError("Pallas decode step holds no tpu_custom_call")
    _, k_logits, _ = k_dec(*dargs)
    _, r_logits, _ = r_dec(*dargs)
    out.update(_gap("decode", k_logits, r_logits))
    tol = out["tol_frac"] = LOGIT_TOL[precision]
    if any(out[f"{step}_max_abs_diff"] > tol * out[f"{step}_ref_max_abs"]
           for step in ("chunk", "decode")):
        raise AssertionError(f"Pallas vs reference logits beyond tolerance "
                             f"({precision}): {out}")
    return out


def _gap(step: str, got, ref) -> dict:
    got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
    diff = got - ref
    return {f"{step}_max_abs_diff": float(jnp.max(jnp.abs(diff))),
            f"{step}_ref_max_abs": float(jnp.max(jnp.abs(ref))),
            f"{step}_rel_rms": float(jnp.sqrt(jnp.mean(diff ** 2)
                                              / jnp.mean(ref ** 2)))}


def _peak_gib() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2**30:.2f} GiB"


def run_phases(cfg, params, prompts) -> None:
    """Phases (a)-(d) in order; raises on the first that fails."""
    for precision in ("float", "int8"):
        if precision == "int8":
            # the int8 phases hold int8 weights only: quantizing once here
            # frees the 7 GiB of float projections the servers never read
            params = serving_params(params, policy_for(precision),
                                    cfg.activation_dtype)
            jax.block_until_ready(params)
        tag = "a" if precision == "float" else "d"
        tokens, m = serve(cfg, params, prompts, precision=precision,
                          use_artifact=False)
        print(f"phase ({tag}) {precision} serve ok: "
              f"{len(tokens)} requests x {MAX_NEW} tokens, "
              f"capacity {SLOTS}x{m['capacity']} rows in "
              f"{m['block_size']}-row blocks, kernels "
              f"{m['tpu_custom_call']}; first run "
              f"{m['first_run_s']:.1f}s incl. compile, "
              f"peak {_peak_gib()} (smoke readings)")
        if precision == "float":
            t1 = time.perf_counter()
            art_tokens, _ = serve(cfg, params, prompts, precision=precision,
                                  use_artifact=True)
            if art_tokens != tokens:
                diff = [i for i, (a, b) in enumerate(zip(tokens, art_tokens))
                        if a != b]
                raise AssertionError(f"artifact decode diverged on "
                                     f"requests {diff}")
            print(f"phase (b) artifact decode ok: same tokens for all "
                  f"{len(tokens)} requests "
                  f"({time.perf_counter() - t1:.1f}s, smoke reading)")
        gc.collect()
        t1 = time.perf_counter()
        diffs = compare_paths(cfg, params, precision=precision,
                              capacity=m["capacity"])
        tag = "c" if precision == "float" else "d"
        print(f"phase ({tag}) {precision} pallas vs ref ok: "
              f"{json.dumps(diffs)} ({time.perf_counter() - t1:.1f}s, "
              f"peak {_peak_gib()}, smoke readings)")
        gc.collect()


def main() -> int:
    pinned = os.environ.get("REPRO_KERNEL_PATH") or None
    if pinned not in (None, "pallas"):
        print(f"REPRO_KERNEL_PATH={pinned} pins the kernels off the chip "
              f"path; unset it or set it to pallas", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX sees {dev.platform} devices; this smoke run "
              f"never falls back to the CPU", file=sys.stderr)
        return 1
    if ops.resolve_path() != "pallas":
        print(f"kernel path resolves to {ops.resolve_path()}, not pallas",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f"  compile cache: {cache_dir}")

    cfg = configs.get(ARCH)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(SEED))
    jax.block_until_ready(params)
    print(f"{ARCH}: {param_count(cfg):,} params, init "
          f"{time.perf_counter() - t0:.1f}s (smoke reading)")
    prompts = workload(cfg.vocab_size)
    print(f"workload: {len(prompts)} requests, prompt lengths "
          f"{min(map(len, prompts))}-{max(map(len, prompts))}, "
          f"{MAX_NEW} new tokens, {SLOTS} slots, chunk {CHUNK}")

    run_phases(cfg, params, prompts)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
