#!/usr/bin/env bash
# End-to-end smoke: the paper's quickstart loop + the serving benchmark
# in tiny mode, on both sides of the precision axis (paper C5: the same
# engine serves float and full-int8).  Finishes in a few minutes on CPU.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== quickstart (impulse train -> quantize -> estimate -> compile) ==="
python examples/quickstart.py

echo
echo "=== serve bench (static vs continuous batching, tiny, float) ==="
python benchmarks/serve_bench.py --tiny --precision float

echo
echo "=== serve bench (float vs int8 end-to-end, tiny) ==="
python benchmarks/serve_bench.py --tiny --precision int8

echo
echo "=== chunked-prefill serving (pad-free admission, float + int8) ==="
# Run the chunked pad-free admission path end-to-end through the Pallas
# interpreter (chunk-prefill + flash-decode kernels) on both sides of
# the precision axis: the chunk-size sweep exercises ragged final
# chunks, interleaved prefill/decode, and the kv_len fill metrics.
REPRO_KERNEL_PATH=interpret python benchmarks/serve_bench.py --tiny \
    --precision float --prefill-chunk 4 16
REPRO_KERNEL_PATH=interpret python benchmarks/serve_bench.py --tiny \
    --precision int8 --prefill-chunk 4

echo
echo "=== paged KV serving (block tables, prefix reuse, preemption) ==="
# Paged-pool engine end-to-end through the Pallas interpreter, float AND
# int8 in one run: a shared-prefix workload against a pool sized to
# force preempt-and-recompute (the summary line reports preemptions ≥ 1,
# prefix-hit rate, and live-KV HBM vs the contiguous rectangle);
# token-exactness vs the contiguous engine is asserted inside the bench.
# Pool blocks are the kernel's 128-row tile, so prompts and budgets are
# long enough for a slot to span several blocks.
REPRO_KERNEL_PATH=interpret python benchmarks/serve_bench.py \
    --requests 6 --slots 3 --max-prompt 160 --max-new 120 \
    --precision int8 --paged-only --pool-frac 0.34

echo
echo "=== decode-kernel parity (Pallas lowering via interpret mode) ==="
# Pin every kernels/ops dispatch to the Pallas interpreter so the
# flash-decode lowering is exercised on every smoke run, not just on TPU:
# kernel-vs-ref parity plus token-exact continuous serving through it.
REPRO_KERNEL_PATH=interpret python -m pytest -q tests/test_flash_decode.py
