"""Elastic restore round-trip, fused-roofline credit, pod estimator
adapter, quantized-impulse artifact parity."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint.checkpointer import Checkpointer
from repro.core.arch import SHAPES
from repro.core.estimator import pod_estimate_from_report
from repro.launch.elastic import build_mesh, elastic_restore, plan_rescale
from repro.models.params import init_params, logical_axes
from repro.roofline.hw import V5E
from repro.roofline.model import (RooflineReport, attention_score_traffic,
                                  fused_adjustment, model_flops)
from repro.sharding.policy import make_rules


def test_elastic_restore_cycle(tmp_path):
    """save → 'lose nodes' → restore resharded onto a smaller mesh."""
    cfg = configs.get_smoke("internlm2-1.8b")
    params = init_params(cfg, jax.random.key(0))
    ck = Checkpointer(tmp_path)
    ck.save(10, params)

    plan = plan_rescale({"data": 1, "model": 1}, 1)  # host-scale shrink
    mesh = build_mesh(plan.new_shape)
    rules = make_rules("tp")
    restored, _ = elastic_restore(ck, params, rules, logical_axes(cfg),
                                  mesh)
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, restored)
    assert max(jax.tree.leaves(diffs)) == 0.0


def test_fused_credit_only_for_attention():
    ssm = configs.get("falcon-mamba-7b")
    dense = configs.get("internlm2-1.8b")
    shape = SHAPES["prefill_32k"]
    assert attention_score_traffic(ssm, shape, 256) == 0.0
    assert attention_score_traffic(dense, shape, 256) > 0.0
    # decode gets no credit (scores are negligible there)
    assert attention_score_traffic(dense, SHAPES["decode_32k"], 256) == 0.0
    # sliding-window arch gets less credit per layer than dense S^2
    gem = configs.get("gemma3-4b")
    full = gem.replace(sliding_window=0, local_global_ratio=0)
    assert (attention_score_traffic(gem, shape, 256)
            < attention_score_traffic(full, shape, 256))


def test_fused_adjustment_improves_memory_bound_cell():
    cfg = configs.get("internlm2-1.8b")
    shape = SHAPES["prefill_32k"]
    rep = RooflineReport(
        arch=cfg.name, shape=shape.name, mesh="16x16", n_chips=256,
        hlo_flops=0.197 * V5E.peak_flops_bf16,
        hlo_bytes=0, hlo_bytes_min=0.78 * V5E.hbm_bandwidth,
        collective_bytes=0.31 * V5E.ici_bandwidth,
        collective_detail={}, per_device_hbm=2 * 2**30,
        model_flops=model_flops(cfg, shape)).finalize()
    adj = fused_adjustment(cfg, shape, rep)
    assert adj["roofline_fraction_fused"] > rep.roofline_fraction
    assert adj["t_memory_min_fused_s"] < rep.t_memory_min


def test_pod_estimator_adapter():
    row = {"mesh": "16x16", "t_compute_s": 0.5, "t_memory_s": 2.0,
           "t_memory_min_s": 0.8, "t_collective_s": 0.3,
           "hbm_gib": 12.0, "fits_hbm": True}
    e = pod_estimate_from_report(row)
    assert e.fits
    assert abs(e.nn_latency_ms - 800.0) < 1e-6   # binding term = mem lower
    assert "tpu-v5e-pod" in e.target


def test_dryrun_matrix_complete_on_disk():
    """The shipped dry-run matrix covers all 80 cells with no errors and
    the DESIGN.md skip policy."""
    import glob
    files = glob.glob("experiments/dryrun/*.json")
    if len(files) < 80:
        pytest.skip("dry-run matrix not generated in this environment")
    status = {}
    for f in files:
        d = json.load(open(f))
        status.setdefault(d["status"], 0)
        status[d["status"]] += 1
    assert status.get("error", 0) == 0
    assert status.get("skipped", 0) == 14          # 7 archs × 2 meshes
    assert status.get("ok", 0) == 66


def test_compile_cache_dir(monkeypatch, tmp_path):
    """Entry points keep JAX's compile cache in $JAX_COMPILATION_CACHE_DIR
    (setting no other directory) or else in the git-ignored
    ``<repo>/.jax_cache``."""
    from pathlib import Path

    from repro.launch import compile_cache
    repo = Path(compile_cache.__file__).resolve().parents[3]
    saved = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable() == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", saved)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
