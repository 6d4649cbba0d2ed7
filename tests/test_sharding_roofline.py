"""Sharding policy unit tests + HLO analyzer correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.roofline.collect import (analyze_module, parse_module,
                                    scan_trip_counts)
from repro.roofline.hw import V5E
from repro.roofline.model import RooflineReport
from repro.sharding.policy import logical_to_pspec, make_rules


class FakeMesh:
    """Duck-typed mesh for pspec unit tests (shape dict only)."""
    def __init__(self, shape):
        self.shape = shape


RULES = make_rules("tp")
MESH = FakeMesh({"data": 16, "model": 16})


def test_pspec_basic():
    spec = logical_to_pspec(("p_dmodel", "p_heads"), RULES, MESH,
                            (4096, 2048))
    assert spec == P("data", "model")


def test_pspec_divisibility_fallback():
    # 4 kv heads can't split 16 ways -> replicated
    spec = logical_to_pspec(("act_batch", "act_kv_seq", "act_kv_heads", None),
                            RULES, MESH, (32, 1024, 4, 128))
    assert spec == P("data")


def test_pspec_no_double_axis_use():
    rules = make_rules("tp", decode=True)
    # batch takes "data"; cache_seq falls back to the remaining "model"
    spec = logical_to_pspec(("act_batch", "act_cache_seq", None, None),
                            rules, MESH, (128, 32768, 8, 128))
    assert spec == P("data", "model")
    # batch=1 can't use "data" -> cache seq gets both axes
    spec = logical_to_pspec(("act_batch", "act_cache_seq", None, None),
                            rules, MESH, (1, 524288, 8, 128))
    assert spec == P(None, ("data", "model"))


def test_strategies_differ():
    tp = make_rules("tp")
    cp = make_rules("cp")
    sp = make_rules("tp_sp")
    assert tp["act_heads"] == "model" and cp["act_heads"] is None
    assert cp["act_seq"] == "model"
    assert sp["act_res_seq"] == "model" and tp["act_res_seq"] is None


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------
def test_analyzer_loop_weighting_exact():
    """Weighted dot flops == analytic for a scanned matmul chain; the
    raw cost_analysis is known NOT to weight loops."""
    w = jax.ShapeDtypeStruct((12, 256, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 256), jnp.float32)

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w @ w.T), None
        return jax.lax.scan(body, x, ws)[0]

    comp = jax.jit(f).lower(w, x).compile()
    wc = analyze_module(comp.as_text())
    expect = 12 * (2 * 8 * 256 * 128 + 2 * 8 * 128 * 256)
    assert wc.flops == expect
    raw = comp.cost_analysis()
    assert raw["flops"] < expect         # the raw one undercounts


def test_analyzer_trip_counts():
    x = jax.ShapeDtypeStruct((4, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((7, 16, 16), jnp.float32)

    def f(ws, x):
        return jax.lax.scan(lambda h, w: (h @ w, None), x, ws)[0]

    txt = jax.jit(f).lower(w, x).compile().as_text()
    assert 7 in scan_trip_counts(txt)


def test_analyzer_bytes_min_le_bytes():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f(a):
        return jnp.tanh(a @ a) + jnp.exp(a)

    wc = analyze_module(jax.jit(f).lower(x).compile().as_text())
    assert 0 < wc.bytes_min <= wc.bytes_accessed


def test_roofline_report_terms():
    rep = RooflineReport(
        arch="x", shape="train_4k", mesh="16x16", n_chips=256,
        hlo_flops=197e12,            # exactly one second of compute
        hlo_bytes=819e9 * 2,         # two seconds of memory (upper)
        hlo_bytes_min=819e9 * 0.5,   # half a second (lower)
        collective_bytes=200e9 * 0.25,
        collective_detail={}, per_device_hbm=8 * 2 ** 30,
        model_flops=197e12 * 256 * 0.5,
    ).finalize(V5E)
    assert abs(rep.t_compute - 1.0) < 1e-6
    assert rep.bottleneck == "compute"        # judged vs the lower bound
    assert abs(rep.useful_flops_ratio - 0.5) < 1e-6
    assert rep.fits_hbm
    assert abs(rep.roofline_fraction - 0.5) < 1e-6
