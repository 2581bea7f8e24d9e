"""Substrate: optimizer, checkpointing, pipeline, compression, sharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config, reduced_config
from repro.data.pipeline import DataConfig, Pipeline
from repro.distributed.compression import (dequantize_int8,
                                           init_error_feedback,
                                           quantize_int8)
from repro.distributed.sharding import DEFAULT_RULES, spec_for
from repro.launch.mesh import make_local_mesh
from repro.optim import adamw
from repro.optim.adamw import OptConfig


# --------------------------------------------------------------------------- #
# optimizer
# --------------------------------------------------------------------------- #
def test_adamw_descends_quadratic():
    cfg = OptConfig(peak_lr=0.1, warmup_steps=1, decay_steps=1000,
                    weight_decay=0.0, clip_norm=100.0)
    params = {"w": jnp.array([3.0, -2.0])}
    opt = adamw.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    l0 = float(loss(params))
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, opt, m = adamw.update(cfg, g, opt, params)
    assert float(loss(params)) < 0.01 * l0


def test_adamw_clips_gradients():
    cfg = OptConfig(clip_norm=1.0, warmup_steps=1)
    params = {"w": jnp.zeros(3)}
    opt = adamw.init(params)
    g = {"w": jnp.array([1e6, 0.0, 0.0])}
    _, _, m = adamw.update(cfg, g, opt, params)
    assert float(m["grad_norm"]) == pytest.approx(1e6)


def test_schedule_warmup_and_decay():
    cfg = OptConfig(peak_lr=1.0, warmup_steps=10, decay_steps=110,
                    min_lr_ratio=0.1)
    assert float(adamw.schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(adamw.schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(adamw.schedule(cfg, jnp.int32(110))) == pytest.approx(0.1)


# --------------------------------------------------------------------------- #
# checkpointing
# --------------------------------------------------------------------------- #
def test_checkpoint_roundtrip_and_prune(tmp_path):
    d = str(tmp_path)
    tree = {"a": jnp.arange(5), "b": {"c": jnp.ones((2, 3))}}
    for step in (10, 20, 30, 40):
        ckpt.save(d, step, tree, keep=2)
    assert ckpt.latest_step(d) == 40
    files = [f for f in os.listdir(d) if f.endswith(".npz")]
    assert len(files) == 2                       # pruned to keep=2
    step, restored = ckpt.restore(d, tree)
    assert step == 40
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.arange(5))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, {"a": jnp.ones(3)})
    with pytest.raises(ValueError):
        ckpt.restore(d, {"a": jnp.ones(4)})


def test_checkpoint_async(tmp_path):
    d = str(tmp_path)
    t = ckpt.save_async(d, 5, {"x": jnp.ones(2)})
    t.join(timeout=10)
    assert ckpt.latest_step(d) == 5


# --------------------------------------------------------------------------- #
# pipeline
# --------------------------------------------------------------------------- #
def test_pipeline_deterministic_and_resumable():
    cfg = reduced_config(get_config("smollm-360m"))
    p1 = Pipeline(cfg, DataConfig(4, 16, seed=7))
    p2 = Pipeline(cfg, DataConfig(4, 16, seed=7))
    b1, b2 = p1.batch(123), p2.batch(123)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = p1.batch(124)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_pipeline_targets_shifted():
    cfg = reduced_config(get_config("smollm-360m"))
    p = Pipeline(cfg, DataConfig(2, 8))
    b = p.batch(0)
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                  np.asarray(b["targets"][:, :-1]))


# --------------------------------------------------------------------------- #
# compression
# --------------------------------------------------------------------------- #
def test_error_feedback_unbiased_over_steps():
    """EF residual keeps the cumulative quantized sum close to the true sum."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    residual = jnp.zeros(64)
    acc = np.zeros(64)
    for _ in range(50):
        v = g_true + residual
        q, s = quantize_int8(v)
        deq = dequantize_int8(q, s)
        residual = v - deq
        acc += np.asarray(deq)
    np.testing.assert_allclose(acc / 50, np.asarray(g_true), atol=1e-2)


# --------------------------------------------------------------------------- #
# sharding rules
# --------------------------------------------------------------------------- #
def test_spec_for_divisibility_and_uniqueness():
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    P = jax.sharding.PartitionSpec
    # divisible dims get their preferred axes
    assert spec_for((16, 8), ("embed", "mlp"), mesh) == P("data", "model")
    # non-divisible fall back to replication (mixtral: 7 % 4 != 0)
    assert spec_for((7, 8), ("experts", "mlp"), mesh) == P(None, "model")
    # the same mesh axis is never used twice
    s = spec_for((8, 8), ("mlp", "vocab"), mesh)
    axes = [a for a in s if a is not None]
    assert len(axes) == len(set(axes)) <= 1 or axes == ["model"]


def test_spec_for_batch_tuple_rule():
    mesh = jax.sharding.AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    P = jax.sharding.PartitionSpec
    assert spec_for((8, 4), ("batch", None), mesh) == P(("pod", "data"))
    # batch=1 cannot shard
    assert spec_for((1, 4), ("batch", None), mesh) == P()


def test_param_specs_cover_all_archs():
    from repro.configs import list_archs
    from repro.launch import steps as S
    mesh = make_local_mesh()
    for arch in list_archs():
        cfg = reduced_config(get_config(arch))
        sh = S.state_shardings(cfg, mesh)       # must not raise
        assert jax.tree.leaves(sh)


# --------------------------------------------------------------------------- #
# persistent compilation cache placement
# --------------------------------------------------------------------------- #
def test_compile_cache_follows_env_else_fixed_repo_path(monkeypatch):
    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert cc.use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None   # JAX reads env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cc.use_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert path == cc.use_compile_cache()                 # fixed path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
