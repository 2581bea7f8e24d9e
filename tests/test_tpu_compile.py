"""Ahead-of-time compiles of the serving path for a described TPU v5e.

Nothing here runs: each test compiles for one chip of a described
``v5e:2x2`` topology, at smollm-360m widths in bf16 (15 query heads over 5
KV heads, head_dim 64, KV blocks of 8), and checks that the attention
kernels lowered to Mosaic (``tpu_custom_call``) rather than being refused
or interpreted.  The TPU compiler is installed with JAX, so this needs no
chip.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture, never while a module is imported: each
pytest worker then collects the same tests, and only the one given this
file loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, HQ, HKV, D = 8, 15, 5, 64           # smollm-360m attention widths
BS, N_BLOCKS, M = 8, 512, 32           # block size, pool blocks, blocks/slot
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Compile as the chip would: the platform decision in ``ops`` sees
    the CPU backend here, so steer it to Mosaic for this test only, and
    keep the persistent compilation cache off (a TPU executable written
    to it cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda interpret: False)
    monkeypatch.setattr(ops, "serving_impl", lambda: "pallas")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _mosaic_sites(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


SMOLLM = (16, 80, 5, 15, 64, 1281)     # B, M, Hkv, Hq, D, pool blocks as
PHI3 = (8, 64, 32, 32, 96, 513)        # served; phi3-mini: MHA at D 96


@pytest.mark.parametrize("fused,shape", [
    pytest.param(True, (B, M, HKV, HQ, D, N_BLOCKS), id="True"),
    pytest.param(False, (B, M, HKV, HQ, D, N_BLOCKS), id="False"),
    pytest.param(True, SMOLLM, id="True-smollm"),
    pytest.param(False, SMOLLM, id="False-smollm"),
    pytest.param(True, PHI3, id="True-phi3"),
    pytest.param(False, PHI3, id="False-phi3"),
])
def test_paged_attention_compiles_for_v5e(one_chip, for_tpu, fused, shape):
    b, m, hkv, hq, d, n_blocks = shape
    from repro.kernels import ops
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((n_blocks, hkv, BS, d), BF16)
    assert _mosaic_sites(
        lambda q, k, v, t, n: ops.paged_attention(q, k, v, t, n,
                                                  fused=fused),
        s((b, 1, hq, d), BF16), pool, pool, s((b, m), I32),
        s((b,), I32)) >= 1


@pytest.mark.parametrize("c", [8, 5])       # prefill chunk; K=4 verify
def test_paged_prefill_compiles_for_v5e(one_chip, for_tpu, c):
    from repro.kernels import ops
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = s((N_BLOCKS, HKV, BS, D), BF16)
    fn = ops.paged_prefill if c == 8 else ops.paged_verify
    assert _mosaic_sites(fn, s((B, c, HQ, D), BF16), pool, pool,
                         s((B, M), I32), s((B,), I32), s((B,), I32)) >= 1


def test_flash_attention_compiles_for_v5e(one_chip, for_tpu):
    from repro.kernels import ops
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    assert _mosaic_sites(ops.flash_attention, s((4, 128, HQ, D), BF16),
                         s((4, 128, HKV, D), BF16),
                         s((4, 128, HKV, D), BF16)) >= 1


def test_served_decode_window_compiles_for_v5e(one_chip, for_tpu):
    """The served paged decode-window step of a 2-layer smollm-360m at its
    published widths, as ``LMBackend.paged_fns`` builds it."""
    import dataclasses
    from repro.configs import get_config
    from repro.launch.serve import LMBackend
    from repro.models import model
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2)
    backend = LMBackend(cfg, capacity=M * BS)
    assert backend.ctx.impl == "pallas"

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init(cfg, jax.random.PRNGKey(0))))
    pool = jax.tree.map(on_chip, jax.eval_shape(
        lambda: model.init_paged_cache(cfg, 4, 4 * M + 1, BS)))
    vec = jax.ShapeDtypeStruct((4,), I32, sharding=one_chip)
    decode_window = backend.paged_fns(BS, window=4)[2]
    text = decode_window.lower(
        params, pool, jax.ShapeDtypeStruct((4, 1), I32, sharding=one_chip),
        vec, vec, jax.ShapeDtypeStruct((4, M), I32, sharding=one_chip),
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 1
