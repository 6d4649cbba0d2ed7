"""Training substrate: optimizer, grad accumulation, compression,
trainer fault tolerance, checkpoint round trips, elastic restore,
LR finder, serving loop."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.checkpoint.checkpointer import Checkpointer
from repro.core.arch import ShapeConfig
from repro.data.synthetic import lm_batches, token_stream
from repro.launch.elastic import plan_rescale
from repro.models import api
from repro.models.params import init_params
from repro.train import compression as comp
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.train.schedule import lr_finder, warmup_cosine
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = configs.get_smoke("internlm2-1.8b")
    params = init_params(cfg, jax.random.key(0))
    tokens = token_stream(50_000, cfg.vocab_size, seed=1)
    return cfg, params, tokens


def test_train_loss_decreases(tiny_setup, tmp_path):
    cfg, params, tokens = tiny_setup
    params = jax.tree.map(jnp.copy, params)   # donation-safe copy
    step = jax.jit(make_train_step(cfg, opt=AdamWConfig(lr=1e-3)),
                   donate_argnums=(0, 1))
    trainer = Trainer(step, params, adamw_init(params),
                      ckpt_dir=tmp_path / "ck",
                      config=TrainerConfig(total_steps=30, log_every=0,
                                           checkpoint_every=0))
    res = trainer.run(iter(lm_batches(tokens, 8, 32)))
    first = np.mean([h["loss"] for h in res["history"][:5]])
    last = np.mean([h["loss"] for h in res["history"][-5:]])
    assert last < first - 0.3, (first, last)


def test_grad_accumulation_equivalence(tiny_setup):
    """n_micro=4 must match n_micro=1 on the same global batch."""
    cfg, params, tokens = tiny_setup
    batch = next(lm_batches(tokens, 8, 32, seed=3))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    opt = adamw_init(params)
    s1 = make_train_step(cfg, n_microbatch=1, remat="none")
    s4 = make_train_step(cfg, n_microbatch=4, remat="none")
    p1, _, m1 = jax.jit(s1)(params, opt, batch)
    p4, _, m4 = jax.jit(s4)(params, adamw_init(params), batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 2e-2
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        p1, p4)
    assert max(jax.tree.leaves(diffs)) < 5e-2


def test_remat_matches_no_remat(tiny_setup):
    cfg, params, tokens = tiny_setup
    batch = {k: jnp.asarray(v) for k, v in
             next(lm_batches(tokens, 4, 32, seed=5)).items()}
    from repro.models.api import model_fns
    fns = model_fns(cfg)
    g_plain = jax.grad(
        lambda p: fns.forward_train(cfg, p, batch, remat="none")[0])(params)
    g_remat = jax.grad(
        lambda p: fns.forward_train(cfg, p, batch, remat="full")[0])(params)
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), g_plain, g_remat)
    assert max(jax.tree.leaves(diffs)) < 1e-3


def test_gradient_compression_error_feedback():
    """int8-compressed SGD with error feedback tracks uncompressed SGD."""
    rng = np.random.RandomState(0)
    w_true = jnp.asarray(rng.randn(16), jnp.float32)
    x = jnp.asarray(rng.randn(256, 16), jnp.float32)
    y = x @ w_true

    def loss(w):
        return jnp.mean((x @ w - y) ** 2)

    w_ref = w_cmp = jnp.zeros(16)
    residual = {"g": jnp.zeros(16)}
    for _ in range(60):
        g_ref = jax.grad(loss)(w_ref)
        w_ref = w_ref - 0.05 * g_ref
        g = jax.grad(loss)(w_cmp)
        gc, residual = comp.compress_grads({"g": g}, residual, "int8")
        w_cmp = w_cmp - 0.05 * gc["g"]
    assert float(loss(w_cmp)) < 1e-2
    assert abs(float(loss(w_cmp)) - float(loss(w_ref))) < 1e-2


def test_topk_compression_sparsity():
    g = jnp.asarray(np.random.RandomState(0).randn(1000), jnp.float32)
    gc, _ = comp.compress_grads(
        {"g": g}, {"g": jnp.zeros(1000)}, "topk", topk_frac=0.05)
    nz = int(jnp.sum(gc["g"] != 0))
    assert nz <= 55


def test_trainer_crash_restart(tiny_setup, tmp_path):
    """Kill at step 25, resume from checkpoint, finish — the history
    continues from the restored step."""
    cfg, params, tokens = tiny_setup
    step = jax.jit(make_train_step(cfg, opt=AdamWConfig(lr=1e-3)))
    mk = lambda: Trainer(step, params, adamw_init(params),
                         ckpt_dir=tmp_path / "ck2",
                         config=TrainerConfig(total_steps=40,
                                              checkpoint_every=10,
                                              log_every=0,
                                              restore_best=False))
    t1 = mk()
    with pytest.raises(RuntimeError, match="injected node failure"):
        t1.run(iter(lm_batches(tokens, 4, 32)), fail_at=25)
    t2 = mk()
    assert t2.maybe_resume()
    assert t2.step == 20                      # last checkpoint before crash
    res = t2.run(iter(lm_batches(tokens, 4, 32)))
    assert t2.step == 40
    assert np.isfinite(res["final_loss"])


def test_checkpointer_atomicity(tmp_path):
    """A checkpoint without a manifest is invisible."""
    ck = Checkpointer(tmp_path)
    tree = {"a": jnp.arange(6.0), "b": {"c": jnp.ones((2, 3))}}
    ck.save(5, tree)
    # simulate a partial write: directory without manifest
    bad = tmp_path / "step_00000009"
    bad.mkdir()
    (bad / "a.npy").write_bytes(b"garbage")
    assert ck.latest_step() == 5
    restored, _ = ck.restore(tree)
    np.testing.assert_allclose(restored["a"], tree["a"])


def test_elastic_rescale_plan():
    plan = plan_rescale({"pod": 2, "data": 16, "model": 16}, 384)
    assert plan.new_shape["model"] == 16
    total = 1
    for v in plan.new_shape.values():
        total *= v
    assert total <= 384
    # model axis survives even a brutal shrink
    plan2 = plan_rescale({"data": 16, "model": 16}, 48)
    assert plan2.new_shape == {"data": 2, "model": 16}


def test_lr_finder_picks_reasonable_lr():
    """Quadratic bowl: finder must propose an lr that converges."""
    w0 = jnp.asarray([3.0])

    def probe(lr):
        w = w0
        for _ in range(5):
            w = w - lr * jax.grad(lambda v: jnp.sum(v ** 2))(w)
        return float(jnp.sum(w ** 2))

    lr, curve = lr_finder(probe, lr_min=1e-5, lr_max=10.0, n_probe=15)
    assert 1e-5 <= lr <= 1.1
    w = w0
    for _ in range(50):
        w = w - lr * jax.grad(lambda v: jnp.sum(v ** 2))(w)
    assert float(jnp.sum(w ** 2)) < 9.0


def test_warmup_cosine_shape():
    lrs = [float(warmup_cosine(s, base_lr=1.0, warmup=10, total=100))
           for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0
    assert max(lrs) <= 1.0 + 1e-6
    assert lrs[-1] < 0.2


def test_batch_server_generates(tiny_setup):
    from repro.serve.server import PagedBatchServer
    cfg, params, _ = tiny_setup
    server = PagedBatchServer(cfg, params, slots=2, max_prompt=8,
                              max_new_tokens=4)
    rng = np.random.RandomState(0)
    server.submit([rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
                   for _ in range(4)])
    m = server.run()
    assert m["requests"] == 4
    assert m["tokens_generated"] == 16
    assert m["tokens_per_s"] > 0
