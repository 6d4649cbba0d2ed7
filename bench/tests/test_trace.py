"""The trace reduction, pinned on a trace recorded on a TPU v5e: the
``internlm2-chat`` server serving four short requests (chunk-prefill and
decode steps, Pallas kernels), gzipped beside this file."""
import gzip
import shutil

import pytest

from bench import trace as T
from bench.harness import BENCH
from bench.metrics import decode_attn_roofline, decode_step_ms, \
    prefill_chunk_ms

FIXTURE = BENCH / "tests" / "data" / "internlm2-chat.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(FIXTURE) as src, open(d / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    chips = T.load(d.parents[2])
    assert list(chips) == ["/device:TPU:0"]
    return chips["/device:TPU:0"]


def test_events_and_busy(chip):
    assert (len(chip.modules), len(chip.ops)) == (21, 18291)
    assert T.busy_ns(chip) == 562054361


def test_programs(chip):
    p = T.programs(chip)
    assert p["jit_decode_step"] == (9, pytest.approx(0.325747509))
    assert p["jit_paged_chunk_step"] == (6, pytest.approx(0.236312517))


def test_kernels_inside_their_programs(chip):
    # 24 layers: one attention kernel call per layer and execution
    assert T.kernel(chip, decode_attn_roofline.KERNEL,
                    decode_attn_roofline.PROGRAM) == (
        216, pytest.approx(0.004600332))
    assert T.kernel(chip, r"^%flash_chunk_prefill\.\d+ = ",
                    "jit_paged_chunk_step") == (
        144, pytest.approx(0.002684169))


def test_breakdown(chip):
    top = T.top_ops(chip, 3)
    assert [n for n, _ in top] == ["jit_decode_step/%copy.60 copy",
                                   "jit_decode_step/%copy.62 copy",
                                   "jit_decode_step/%convert.28 convert"]
    assert top[0][1] == pytest.approx(0.033077044)
    gaps = T.idle_gaps(chip, 2)
    assert gaps[0] == ("before jit_paged_chunk_step",
                       pytest.approx(0.003826674))


def test_step_readers(chip):
    ctx = {"chip": chip}
    assert decode_step_ms.read(ctx) == pytest.approx(325.747509 / 9)
    assert prefill_chunk_ms.read(ctx) == pytest.approx(236.312517 / 6)
