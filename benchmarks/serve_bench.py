"""Serving benchmark: static vs continuous batching × float vs int8
precision × prefill chunk size on a mixed-length synthetic workload
(paper §4.6 + C5 operationalised).

Engines: both run the same greedy decode steps over the same requests —
scheduling is the only variable — so the delta is pure head-of-line
blocking.  Precision: ``--precision int8`` additionally serves the same
seeded workload through the end-to-end int8 path (QTensor weights,
dynamic activation quant, Int8KV cache) and reports tokens/s and
KV-cache HBM bytes side by side with the float baseline — Table 4's
RAM story transposed to the serving tier.  The precision comparison
runs f32 activations (the paper's C5 baseline is float32; bf16 is
emulated on CPU anyway), so the HBM reduction is the honest f32→int8
ratio.

Chunking: ``--prefill-chunk 4 8 16`` sweeps the chunked pad-free
admission axis on the continuous engine — TTFT p50/p95 and the
``kv_read_frac``/``kv_fill_frac`` decode-bandwidth metrics per chunk
size, next to an *estimated* padded-baseline fill (what the retired
left-pad bucket ladder ``(max/4, max/2, max)`` would have kept live:
pad rows sat inside ``kv_len`` and were read every decode step).  The
measured read-fraction drop versus that estimate is the bandwidth the
pad rows used to burn.

The workload generator is seeded (``--seed``) and built ONCE per run:
float-vs-int8, continuous-vs-static, and every chunk size all serve the
identical request mix, so every ratio in the report is apples-to-apples.

Paging: ``--paged`` adds the paged-pool axis — the contiguous engine
vs ``PagedBatchServer`` (block-table memory manager, docs/paged_kv.md)
on a shared-prefix workload, reporting pool utilization (live / total
blocks), prefix-cache hit rate, preemption count, and live-KV HBM
against the contiguous ``slots × capacity`` rectangle; ``--pool-frac``
sizes the pool below the rectangle to force preempt-and-recompute.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--tiny]
          [--artifact] [--precision {float,int8}] [--seed N]
          [--prefill-chunk C ...] [--paged [--pool-frac F]]
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from repro import configs
from repro.launch import compile_cache
from repro.models.params import init_params
from repro.serve.kvcache import kv_block_size
from repro.serve.server import (ContinuousBatchServer, PagedBatchServer,
                                StaticBatchServer)


def shared_prefix_workload(vocab: int, n_requests: int, max_prompt: int,
                           max_new: int, seed: int = 0):
    """Mixed-length workload where every even request opens with one
    common prompt prefix (half the max prompt) — the paged engine's
    prefix cache should serve those blocks once; the contiguous engine
    recomputes and re-stores them per slot.  Seed-determined."""
    rng = np.random.RandomState(seed + 17)
    plen = max(max_prompt // 2, 1)
    prefix = rng.randint(0, vocab, plen).astype(np.int32)
    prompts, budgets = [], []
    for i in range(n_requests):
        if i % 2 == 0:
            n = rng.randint(1, max(2, max_prompt - plen + 1))
            p = np.concatenate([prefix,
                                rng.randint(0, vocab, n).astype(np.int32)])
        else:
            p = rng.randint(0, vocab,
                            rng.randint(3, max_prompt + 1)).astype(np.int32)
        prompts.append(p)
        budgets.append(int(rng.randint(2, max_new + 1)))
    return prompts, budgets


def mixed_workload(vocab: int, n_requests: int, max_prompt: int,
                   max_new: int, seed: int = 0):
    """Bimodal prompts (short/long) with varied generation budgets — the
    adversarial case for static batching.  Fully determined by ``seed``."""
    rng = np.random.RandomState(seed)
    prompts, budgets = [], []
    for i in range(n_requests):
        if i % 2 == 0:
            n = rng.randint(3, max(4, max_prompt // 4))
            b = rng.randint(2, max(3, max_new // 4))
        else:
            n = rng.randint(max_prompt // 2, max_prompt + 1)
            b = rng.randint(max(2, max_new // 2), max_new + 1)
        prompts.append(rng.randint(0, vocab, n).astype(np.int32))
        budgets.append(int(b))
    return prompts, budgets


def _padded_fill_frac_est(server, chunk_metrics) -> float:
    """What ``kv_fill_frac`` would have been under the retired left-pad
    bucket ladder ``(max/4, max/2, max)``: each request's slot carried
    ``bucket(S) − S`` pad rows inside ``kv_len`` for every decode step
    it was live (≈ its generated-token count)."""
    buckets = sorted({max(server.max_prompt // 4, 1),
                      max(server.max_prompt // 2, 1), server.max_prompt})

    def bucket(n):
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    extra = sum((bucket(len(r.prompt)) - len(r.prompt)) * len(r.tokens)
                for r in server.requests.values())
    denom = (chunk_metrics["decode_steps"] * server.n_slots
             * server.capacity)
    return chunk_metrics.get("kv_fill_frac", 0.0) + extra / max(denom, 1)


def _run_engines(cfg, params, prompts, budgets, *, slots, max_prompt,
                 max_new, use_artifact, precision, prefill_chunk=8):
    static = StaticBatchServer(cfg, params, batch_size=slots,
                               max_prompt=max_prompt,
                               prefill_chunk=prefill_chunk,
                               max_new_tokens=max_new, precision=precision)
    static.submit(prompts, max_new_tokens=budgets)
    m_static = static.run()

    cont = ContinuousBatchServer(
        cfg, params, slots=slots, max_prompt=max_prompt,
        prefill_chunk=prefill_chunk, max_new_tokens=max_new,
        use_artifact=use_artifact, precision=precision)
    cont.submit(prompts, max_new_tokens=budgets)
    m_cont = cont.run()
    m_cont["padded_fill_frac_est"] = _padded_fill_frac_est(cont, m_cont)

    # same scheduling-independent outputs → the speedup is real, not a
    # different (cheaper) computation.  Pad-free chunked prefill makes
    # this exact for EVERY family — SSM/hybrid recurrences included.
    s_reqs = list(static.requests.values())
    tokens_match = ([r.tokens for r in s_reqs]
                    == [cont.requests[i].tokens for i in
                        sorted(cont.requests)])
    assert tokens_match, f"engines diverged ({cfg.name}, {precision})"
    return {"static": m_static, "continuous": m_cont,
            "tokens_match": bool(tokens_match),
            "tokens_per_s_speedup": (m_cont["tokens_per_s"]
                                     / max(m_static["tokens_per_s"], 1e-9))}


def _run_paged(cfg, params, *, slots, max_prompt, max_new, precision,
               pool_frac, n_requests, seed, prefill_chunk=8):
    """Paged-pool axis: contiguous vs paged engine on a shared-prefix
    mixed-length workload (same requests, token-exactness asserted).

    The paged server runs with the kernel's own block size
    (``kv_block_size`` of the slot capacity) and a pool of
    ``pool_frac`` × the contiguous rectangle's blocks — under 1.0 the
    engine must preempt-and-recompute to stay correct, which the report
    counts.  Reported: tokens/s both engines, pool utilization (live /
    total blocks), prefix-cache hit rate, and live-KV HBM vs the
    contiguous ``slots × capacity`` rectangle."""
    prompts, budgets = shared_prefix_workload(
        cfg.vocab_size, n_requests, max_prompt, max_new, seed)
    cont = ContinuousBatchServer(
        cfg, params, slots=slots, max_prompt=max_prompt,
        prefill_chunk=prefill_chunk, max_new_tokens=max_new,
        precision=precision)
    cont.submit(prompts, max_new_tokens=budgets)
    m_cont = cont.run()

    bs = kv_block_size(cont.capacity)
    n_rect = slots * cont.capacity // bs
    pool = max(int(pool_frac * n_rect), cont.capacity // bs)
    paged = PagedBatchServer(
        cfg, params, slots=slots, max_prompt=max_prompt,
        prefill_chunk=prefill_chunk, max_new_tokens=max_new,
        precision=precision, block_size=bs, pool_blocks=pool)
    paged.submit(prompts, max_new_tokens=budgets)
    m_paged = paged.run()

    # same tokens out of both engines — paging, sharing, and preemption
    # are pure memory-management concerns, never visible in the stream
    tokens_match = ([r.tokens for r in cont.requests.values()]
                    == [paged.requests[i].tokens
                        for i in sorted(paged.requests)])
    assert tokens_match, f"paged engine diverged ({cfg.name}, {precision})"
    baseline = m_cont["kv_cache_bytes"]
    return {
        "contiguous": m_cont, "paged": m_paged,
        "tokens_match": bool(tokens_match),
        "tokens_per_s_ratio": (m_paged["tokens_per_s"]
                               / max(m_cont["tokens_per_s"], 1e-9)),
        "kv_rect_bytes": baseline,
        "kv_live_bytes_peak": m_paged.get("kv_live_bytes_peak", 0),
        "kv_live_vs_rect": (m_paged.get("kv_live_bytes_peak", 0)
                            / max(baseline, 1)),
    }


def _run_chunk_axis(cfg, params, prompts, budgets, *, slots, max_prompt,
                    max_new, precision, chunks):
    """Continuous engine only, one run per chunk size, same workload."""
    rows = {}
    for c in chunks:
        cont = ContinuousBatchServer(
            cfg, params, slots=slots, max_prompt=max_prompt,
            prefill_chunk=c, max_new_tokens=max_new, precision=precision)
        cont.submit(prompts, max_new_tokens=budgets)
        m = cont.run()
        m["padded_fill_frac_est"] = _padded_fill_frac_est(cont, m)
        rows[c] = m
    return rows


def run_bench(arch: str = "internlm2-1.8b", *, n_requests: int = 12,
              slots: int = 4, max_prompt: int = 32, max_new: int = 24,
              use_artifact: bool = False, seed: int = 0,
              precision: str = "float", prefill_chunks=None,
              paged_pool_frac=None, paged_only: bool = False):
    cfg = configs.get_smoke(arch)
    if precision == "int8":
        # precision axis: pin f32 activations so the float baseline is
        # the paper's C5 comparison point (and CPU-fast).
        cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    prompts, budgets = mixed_workload(cfg.vocab_size, n_requests,
                                      max_prompt, max_new, seed)

    kw = dict(slots=slots, max_prompt=max_prompt, max_new=max_new,
              use_artifact=use_artifact)
    report = {"arch": arch, "requests": n_requests, "slots": slots,
              "seed": seed, "precision": precision}
    if not paged_only:
        report["float"] = _run_engines(cfg, params, prompts, budgets,
                                       precision="float", **kw)
        if precision == "int8":
            report["int8"] = _run_engines(cfg, params, prompts, budgets,
                                          precision="int8", **kw)
            fb = report["float"]["continuous"]["kv_cache_bytes"]
            qb = report["int8"]["continuous"]["kv_cache_bytes"]
            report["kv_cache_hbm_reduction"] = fb / max(qb, 1)
    if prefill_chunks:
        report["chunk_axis"] = _run_chunk_axis(
            cfg, params, prompts, budgets, slots=slots,
            max_prompt=max_prompt, max_new=max_new, precision=precision,
            chunks=prefill_chunks)
    if paged_pool_frac is not None:
        pkw = dict(slots=slots, max_prompt=max_prompt, max_new=max_new,
                   pool_frac=paged_pool_frac, n_requests=n_requests,
                   seed=seed)
        report["paged"] = {"float": _run_paged(cfg, params,
                                               precision="float", **pkw)}
        if precision == "int8":
            report["paged"]["int8"] = _run_paged(cfg, params,
                                                 precision="int8", **pkw)
    if not paged_only:
        # legacy top-level keys (float engine comparison)
        report.update({k: report["float"][k] for k in
                       ("static", "continuous", "tokens_match",
                        "tokens_per_s_speedup")})
    return report


def _decode_hbm_note(res, tag):
    """Per-decode-step KV HBM bytes: the full slots × capacity rectangle
    vs what the kv_len-bounded flash-decode kernel reads (exact pad-free
    fill, whole KV blocks).  Wall-clock effect needs TPU; the byte
    estimate prices full-attention KV leaves — window-bounded ring
    caches are carried at the same fraction as an approximation."""
    c = res["continuous"]
    full = c.get("kv_cache_bytes", 0)
    frac = c.get("kv_read_frac")
    if not full or frac is None:
        return None
    pad = c.get("padded_fill_frac_est")
    pad_note = (f"; padded-baseline fill est {pad:.1%}"
                if pad is not None else "")
    return (f"[{tag}] decode-step KV read: full-capacity scan {full:,} B"
            f" → kv_len-bounded {int(full * frac):,} B"
            f" ({frac:.0%} of capacity at kernel-block granularity;"
            f" exact pad-free fill {c.get('kv_fill_frac', 0):.1%}"
            f"{pad_note})")


def _print_engine_lines(tag, res):
    s, c = res["static"], res["continuous"]
    print(f"[{tag}] static     : {s['tokens_per_s']:9.1f} tok/s  "
          f"ttft p50 {s['ttft_p50_s'] * 1e3:7.1f} ms  "
          f"p95 {s['ttft_p95_s'] * 1e3:7.1f} ms  "
          f"decode_steps {s['decode_steps']}")
    print(f"[{tag}] continuous : {c['tokens_per_s']:9.1f} tok/s  "
          f"ttft p50 {c['ttft_p50_s'] * 1e3:7.1f} ms  "
          f"p95 {c['ttft_p95_s'] * 1e3:7.1f} ms  "
          f"decode_steps {c['decode_steps']}  "
          f"slot_util {c.get('slot_utilization', 0):.2f}  "
          f"kv_hbm {c.get('kv_cache_bytes', 0):,} B")
    print(f"[{tag}] speedup    : {res['tokens_per_s_speedup']:.2f}x tokens/s")


def _print_paged(tag, res):
    c, p = res["contiguous"], res["paged"]
    print(f"[{tag}] contiguous : {c['tokens_per_s']:9.1f} tok/s  "
          f"kv_hbm {c['kv_cache_bytes']:,} B (slots × capacity rectangle)")
    print(f"[{tag}] paged      : {p['tokens_per_s']:9.1f} tok/s  "
          f"pool {p['pool_blocks']}×{p['block_size']}  "
          f"util {p.get('pool_utilization', 0):.2f}  "
          f"live-KV peak {res['kv_live_bytes_peak']:,} B "
          f"({res['kv_live_vs_rect']:.0%} of rectangle)  "
          f"prefix-hit {p['prefix_hit_rate']:.0%}  "
          f"preemptions {p['preemptions']}")


def _print_chunk_axis(rows):
    print("\nprefill-chunk axis (continuous engine, same workload):")
    print("  C   tok/s   ttft_p50   ttft_p95   kv_read  kv_fill  "
          "padded_est")
    for c, m in sorted(rows.items()):
        print(f"{c:>3} {m['tokens_per_s']:7.1f} "
              f"{m['ttft_p50_s'] * 1e3:8.1f}ms {m['ttft_p95_s'] * 1e3:8.1f}ms"
              f" {m.get('kv_read_frac', 0):8.0%} "
              f"{m.get('kv_fill_frac', 0):8.1%} "
              f"{m.get('padded_fill_frac_est', 0):8.1%}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--artifact", action="store_true")
    ap.add_argument("--precision", choices=("float", "int8"),
                    default="float",
                    help="int8 additionally serves the identical workload"
                         " end-to-end int8 and reports the KV-cache HBM"
                         " delta vs float")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload seed (same seed ⇒ identical request mix"
                         " across engines, precisions, and chunk sizes)")
    ap.add_argument("--prefill-chunk", type=int, nargs="+", default=None,
                    help="sweep chunked-admission chunk sizes on the"
                         " continuous engine (TTFT + kv-read/fill per C)")
    ap.add_argument("--paged", action="store_true",
                    help="paged-pool axis: contiguous vs paged engine on"
                         " a shared-prefix workload — pool utilization,"
                         " prefix-hit rate, live-KV HBM vs the rectangle")
    ap.add_argument("--paged-only", action="store_true",
                    help="run ONLY the paged axis (skip the static-vs-"
                         "continuous engine matrix — the paged axis"
                         " builds its own contiguous baseline)")
    ap.add_argument("--pool-frac", type=float, default=0.75,
                    help="paged pool size as a fraction of the contiguous"
                         " slots × capacity rectangle (< 1.0 forces"
                         " preempt-and-recompute under load)")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-sized run for scripts/smoke.sh")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.tiny:
        args.requests, args.slots = 6, 2
        args.max_prompt, args.max_new = 16, 8

    paged = args.paged or args.paged_only
    rep = run_bench(args.arch, n_requests=args.requests, slots=args.slots,
                    max_prompt=args.max_prompt, max_new=args.max_new,
                    use_artifact=args.artifact, seed=args.seed,
                    precision=args.precision,
                    prefill_chunks=args.prefill_chunk,
                    paged_pool_frac=args.pool_frac if paged else None,
                    paged_only=args.paged_only)
    print(json.dumps(rep, indent=1))
    print()
    if "float" in rep:
        _print_engine_lines("float", rep["float"])
        note = _decode_hbm_note(rep["float"], "float")
        if note:
            print(note)
    if "int8" in rep:
        _print_engine_lines("int8 ", rep["int8"])
        note = _decode_hbm_note(rep["int8"], "int8 ")
        if note:
            print(note)
        print(f"\nkv-cache HBM: float "
              f"{rep['float']['continuous']['kv_cache_bytes']:,} B  →  int8 "
              f"{rep['int8']['continuous']['kv_cache_bytes']:,} B  "
              f"({rep['kv_cache_hbm_reduction']:.2f}x reduction)")
    if "chunk_axis" in rep:
        _print_chunk_axis(rep["chunk_axis"])
    if "paged" in rep:
        print("\npaged-pool axis (shared-prefix workload, block-table"
              " memory manager):")
        for tag, res in rep["paged"].items():
            _print_paged(f"paged/{tag}", res)


if __name__ == "__main__":
    main()
