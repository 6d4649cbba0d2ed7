"""Per-layer readers on the recorded trace with stated counters: each
reads what its docstring says, and a reader with nothing to read
returns nothing."""
import json

import pytest

from bench import flops as F
from bench.harness import BENCH
from bench.metrics import (decode_attn_roofline, idle_share, slot_occupancy,
                           step_mfu)
from bench.models import dense_gqa
from bench.tests.test_trace import chip  # noqa: F401  (fixture)

CONFIG = json.loads((BENCH / "configs" / "internlm2-1.8b.json").read_text())
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
STATS = {"decode_steps": 100, "prefill_chunks": 10, "slot_utilization": 0.5,
         "kv_read_frac": 0.25, "kv_fill_frac": 0.2}


def _ctx(chip, **kw):
    ctx = {"chip": chip, "config": CONFIG, "stats": dict(STATS),
           "peaks": PEAKS, "capacity": 1536, "busy_s": 0.5,
           "window_s": 0.6, "prompt_tokens": 1000,
           "prompt_positions": 200000.0}
    ctx.update(kw)
    return ctx


def test_nothing_to_read(chip):  # noqa: F811
    for mod in (decode_attn_roofline, idle_share, step_mfu):
        assert mod.read(_ctx(None)) is None
    assert slot_occupancy.read(_ctx(chip, stats={})) is None


def test_simple_readers(chip):  # noqa: F811
    assert slot_occupancy.read(_ctx(chip)) == pytest.approx(50.0)
    assert idle_share.read(_ctx(chip)) == pytest.approx(100 / 6)


def test_decode_attn_roofline(chip):  # noqa: F811
    rows = 16 * 1536
    ops, nbytes = F.decode_attention_call(CONFIG["model"], "float", 16,
                                          0.25 * rows, 0.2 * rows)
    least = F.least_seconds(ops, nbytes, PEAKS)
    assert least == pytest.approx(nbytes / 819e9)      # bytes bound
    per_call = 0.004600332 / 216
    assert decode_attn_roofline.read(_ctx(chip)) == pytest.approx(
        100 * least / per_call)


def test_step_mfu(chip):  # noqa: F811
    m = CONFIG["model"]
    dec = dense_gqa.token_flops(m, "float", 0.2 * 16 * 1536 / 8)
    pre = dense_gqa.token_flops(m, "float", 200.0)
    total = 9 * 8 * dec["bf16"] + 6 * 100 * pre["bf16"]
    assert step_mfu.read(_ctx(chip)) == pytest.approx(
        100 * total / 197e12 / 0.6)
