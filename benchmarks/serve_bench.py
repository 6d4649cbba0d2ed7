"""Serving benchmark (CPU wall clock): ``PagedBatchServer`` × float vs
int8 precision × prefill chunk size × KV pool size on seeded synthetic
workloads (paper §4.6 + C5 operationalised).

Precision: ``--precision int8`` additionally serves the same seeded
workload through the end-to-end int8 path (QTensor weights, dynamic
activation quant, Int8KV cache) and reports tokens/s and KV-cache HBM
bytes side by side with the float run — Table 4's RAM story transposed
to the serving tier.  The precision comparison runs f32 activations
(the paper's C5 baseline is float32; bf16 is emulated on CPU anyway),
so the HBM reduction is the honest f32→int8 ratio.

Chunking: ``--prefill-chunk 4 8 16`` sweeps the chunked pad-free
admission axis — TTFT p50/p95 and the ``kv_read_frac``/``kv_fill_frac``
decode-bandwidth metrics per chunk size.

Pool: ``--pool-frac F`` serves a shared-prefix workload twice, with the
full ``slots × capacity`` pool and with ``F`` × its blocks, asserts the
token streams match, and reports pool utilization (live / total
blocks), prefix-cache hit rate, preemption count, and peak live-KV HBM
against the full pool.  Under 1.0 the engine must preempt-and-recompute
to stay correct.

The workload generator is seeded (``--seed``) and built ONCE per run:
float-vs-int8 and every chunk size serve the identical request mix, so
every ratio in the report is apples-to-apples.  These are CPU wall
clocks on smoke configs, not device figures; the chip benchmark is
``bench/run.py``.

Run:  PYTHONPATH=src python benchmarks/serve_bench.py [--tiny]
          [--artifact] [--precision {float,int8}] [--seed N]
          [--prefill-chunk C ...] [--pool-frac F]
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
from repro.serve.server import PagedBatchServer


def shared_prefix_workload(vocab: int, n_requests: int, max_prompt: int,
                           max_new: int, seed: int = 0):
    """Mixed-length workload where every even request opens with one
    common prompt prefix (half the max prompt) — the prefix cache should
    serve those blocks once.  Seed-determined."""
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
    """Bimodal prompts (short/long) with varied generation budgets — a
    batch that decodes until its slowest member would hold the short
    requests behind the long ones.  Fully determined by ``seed``."""
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


def _serve(cfg, params, prompts, budgets, *, slots, max_prompt, max_new,
           precision, prefill_chunk=8, use_artifact=False,
           pool_blocks=None):
    """One ``PagedBatchServer`` run; returns (metrics, token streams)."""
    srv = PagedBatchServer(
        cfg, params, slots=slots, max_prompt=max_prompt,
        prefill_chunk=prefill_chunk, max_new_tokens=max_new,
        use_artifact=use_artifact, pool_blocks=pool_blocks,
        precision=precision)
    reqs = srv.submit(prompts, max_new_tokens=budgets)
    m = srv.run()
    return m, [r.tokens for r in reqs]


def _run_pool(cfg, params, *, slots, max_prompt, max_new, precision,
              pool_frac, n_requests, seed, prefill_chunk=8):
    """Pool axis: the full ``slots × capacity`` pool vs ``pool_frac`` ×
    its blocks on a shared-prefix workload (same requests,
    token-exactness asserted).  Under 1.0 the engine must
    preempt-and-recompute to stay correct, which the report counts."""
    prompts, budgets = shared_prefix_workload(
        cfg.vocab_size, n_requests, max_prompt, max_new, seed)
    kw = dict(slots=slots, max_prompt=max_prompt, max_new=max_new,
              precision=precision, prefill_chunk=prefill_chunk)
    m_full, t_full = _serve(cfg, params, prompts, budgets, **kw)
    n_table = m_full["pool_blocks"] // slots
    pool = max(int(pool_frac * m_full["pool_blocks"]), n_table)
    m_pool, t_pool = _serve(cfg, params, prompts, budgets,
                            pool_blocks=pool, **kw)
    # paging, sharing, and preemption are pure memory-management
    # concerns, never visible in the stream
    tokens_match = t_full == t_pool
    assert tokens_match, f"pool size changed tokens ({cfg.name}, {precision})"
    full = m_full["kv_cache_bytes"]
    return {
        "full": m_full, "pooled": m_pool,
        "tokens_match": bool(tokens_match),
        "tokens_per_s_ratio": (m_pool["tokens_per_s"]
                               / max(m_full["tokens_per_s"], 1e-9)),
        "kv_full_bytes": full,
        "kv_live_bytes_peak": m_pool.get("kv_live_bytes_peak", 0),
        "kv_live_vs_full": (m_pool.get("kv_live_bytes_peak", 0)
                            / max(full, 1)),
    }


def run_bench(arch: str = "internlm2-1.8b", *, n_requests: int = 12,
              slots: int = 4, max_prompt: int = 32, max_new: int = 24,
              use_artifact: bool = False, seed: int = 0,
              precision: str = "float", prefill_chunks=None,
              pool_frac=None):
    cfg = configs.get_smoke(arch)
    if precision == "int8":
        # precision axis: pin f32 activations so the float run is the
        # paper's C5 comparison point (and CPU-fast).
        cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    prompts, budgets = mixed_workload(cfg.vocab_size, n_requests,
                                      max_prompt, max_new, seed)

    kw = dict(slots=slots, max_prompt=max_prompt, max_new=max_new)
    report = {"arch": arch, "requests": n_requests, "slots": slots,
              "seed": seed, "precision": precision}
    precisions = ("float", "int8") if precision == "int8" else ("float",)
    for prec in precisions:
        report[prec], _ = _serve(cfg, params, prompts, budgets,
                                 precision=prec, use_artifact=use_artifact,
                                 **kw)
    if precision == "int8":
        report["kv_cache_hbm_reduction"] = (
            report["float"]["kv_cache_bytes"]
            / max(report["int8"]["kv_cache_bytes"], 1))
    if prefill_chunks:
        report["chunk_axis"] = {
            c: _serve(cfg, params, prompts, budgets, precision=precision,
                      prefill_chunk=c, **kw)[0]
            for c in prefill_chunks}
    if pool_frac is not None:
        report["pool"] = {
            prec: _run_pool(cfg, params, precision=prec,
                            pool_frac=pool_frac, n_requests=n_requests,
                            seed=seed, **kw)
            for prec in precisions}
    return report


def _decode_hbm_note(m, tag):
    """Per-decode-step KV HBM bytes: the whole cache vs what the
    kv_len-bounded flash-decode kernel reads (exact pad-free fill, whole
    KV blocks).  Wall-clock effect needs the chip; the byte estimate
    prices full-attention KV leaves — window-bounded ring caches are
    carried at the same fraction as an approximation."""
    full = m.get("kv_cache_bytes", 0)
    frac = m.get("kv_read_frac")
    if not full or frac is None:
        return None
    return (f"[{tag}] decode-step KV read: full-capacity scan {full:,} B"
            f" → kv_len-bounded {int(full * frac):,} B"
            f" ({frac:.0%} of capacity at kernel-block granularity;"
            f" exact pad-free fill {m.get('kv_fill_frac', 0):.1%})")


def _print_run(tag, m):
    print(f"[{tag}] {m['tokens_per_s']:9.1f} tok/s  "
          f"ttft p50 {m['ttft_p50_s'] * 1e3:7.1f} ms  "
          f"p95 {m['ttft_p95_s'] * 1e3:7.1f} ms  "
          f"decode_steps {m['decode_steps']}  "
          f"slot_util {m.get('slot_utilization', 0):.2f}  "
          f"kv_hbm {m.get('kv_cache_bytes', 0):,} B")
    note = _decode_hbm_note(m, tag)
    if note:
        print(note)


def _print_pool(tag, res):
    f, p = res["full"], res["pooled"]
    print(f"[{tag}] full pool  : {f['tokens_per_s']:9.1f} tok/s  "
          f"pool {f['pool_blocks']}×{f['block_size']}  "
          f"kv_hbm {f['kv_cache_bytes']:,} B")
    print(f"[{tag}] pooled     : {p['tokens_per_s']:9.1f} tok/s  "
          f"pool {p['pool_blocks']}×{p['block_size']}  "
          f"util {p.get('pool_utilization', 0):.2f}  "
          f"live-KV peak {res['kv_live_bytes_peak']:,} B "
          f"({res['kv_live_vs_full']:.0%} of the full pool)  "
          f"prefix-hit {p['prefix_hit_rate']:.0%}  "
          f"preemptions {p['preemptions']}")


def _print_chunk_axis(rows):
    print("\nprefill-chunk axis (same workload):")
    print("  C   tok/s   ttft_p50   ttft_p95   kv_read  kv_fill")
    for c, m in sorted(rows.items()):
        print(f"{c:>3} {m['tokens_per_s']:7.1f} "
              f"{m['ttft_p50_s'] * 1e3:8.1f}ms {m['ttft_p95_s'] * 1e3:8.1f}ms"
              f" {m.get('kv_read_frac', 0):8.0%} "
              f"{m.get('kv_fill_frac', 0):8.1%}")


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
                         " across precisions and chunk sizes)")
    ap.add_argument("--prefill-chunk", type=int, nargs="+", default=None,
                    help="sweep chunked-admission chunk sizes (TTFT +"
                         " kv-read/fill per C)")
    ap.add_argument("--pool-frac", type=float, default=None,
                    help="pool axis: serve a shared-prefix workload with"
                         " the full slots × capacity pool and with this"
                         " fraction of its blocks (< 1.0 forces"
                         " preempt-and-recompute under load)")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-sized run for scripts/smoke.sh")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.tiny:
        args.requests, args.slots = 6, 2
        args.max_prompt, args.max_new = 16, 8

    rep = run_bench(args.arch, n_requests=args.requests, slots=args.slots,
                    max_prompt=args.max_prompt, max_new=args.max_new,
                    use_artifact=args.artifact, seed=args.seed,
                    precision=args.precision,
                    prefill_chunks=args.prefill_chunk,
                    pool_frac=args.pool_frac)
    print(json.dumps(rep, indent=1))
    print()
    _print_run("float", rep["float"])
    if "int8" in rep:
        _print_run("int8 ", rep["int8"])
        print(f"\nkv-cache HBM: float "
              f"{rep['float']['kv_cache_bytes']:,} B  →  int8 "
              f"{rep['int8']['kv_cache_bytes']:,} B  "
              f"({rep['kv_cache_hbm_reduction']:.2f}x reduction)")
    if "chunk_axis" in rep:
        _print_chunk_axis(rep["chunk_axis"])
    if "pool" in rep:
        print("\npool axis (shared-prefix workload, block-table memory"
              " manager):")
        for tag, res in rep["pool"].items():
            _print_pool(f"pool/{tag}", res)


if __name__ == "__main__":
    main()
