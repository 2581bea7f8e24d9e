"""Distribution correctness: sharded == unsharded numerics."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import attention_xla

KEY = jax.random.PRNGKey(11)


def test_seq_sharded_attention_core_matches_default():
    """The shard-aware (B, M, rows) attention regrouping is numerically
    identical to the flat path (machinery check on one device)."""
    b, s, hq, hkv, d = 2, 256, 6, 2, 32
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, s, hq, d))
    k = jax.random.normal(k2, (b, s, hkv, d))
    v = jax.random.normal(k3, (b, s, hkv, d))
    base = attention_xla(q, k, v, causal=True, q_chunk=64)
    for shards in (2, 4, 8):
        out = attention_xla(q, k, v, causal=True, q_chunk=64,
                            seq_shards=shards)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"seq_shards={shards}")


_SUBPROCESS_SRC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "{src}")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced_config
    from repro.data.pipeline import DataConfig, Pipeline
    from repro.launch import steps as S
    from repro.models import model
    from repro.optim import adamw
    from repro.optim.adamw import OptConfig

    cfg = reduced_config(get_config("{arch}"))
    pipe = Pipeline(cfg, DataConfig(8, 16, seed=3))
    batch = pipe.batch(0)
    params = model.init(cfg, jax.random.PRNGKey(0))
    state = {{"params": params, "opt": adamw.init(params)}}
    opt_cfg = OptConfig(peak_lr=1e-3, warmup_steps=2, decay_steps=50)

    # drop-free MoE capacity: per-shard capacity semantics otherwise differ
    # (legitimately) between the local and EP paths
    capf = cfg.n_experts / cfg.top_k if cfg.is_moe else 1.25
    # single-device reference
    ctx0 = S.make_context(None, moe_capacity_factor=capf)
    step0 = jax.jit(S.build_train_step(cfg, opt_cfg, ctx0))
    s0, m0 = step0(state, batch)
    # 2x4 production-axis mesh
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    ctx1 = S.make_context(mesh, moe_capacity_factor=capf)
    sh = S.state_shardings(cfg, mesh)
    from repro.distributed import sharding as shd
    bsh = shd.input_shardings(batch, mesh, 8)
    step1 = jax.jit(S.build_train_step(cfg, opt_cfg, ctx1),
                    in_shardings=(sh, bsh))
    s1, m1 = step1(state, batch)
    l0, l1 = float(m0["loss"]), float(m1["loss"])
    g0, g1 = float(m0["grad_norm"]), float(m1["grad_norm"])
    assert abs(l0 - l1) < 5e-3, (l0, l1)
    assert abs(g0 - g1) / max(g0, 1e-6) < 2e-2, (g0, g1)
    print(f"OK loss {{l0:.5f}}=={{l1:.5f}} gnorm {{g0:.4f}}=={{g1:.4f}}")
""")


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen2.5-3b",
                                  "olmoe-1b-7b", "rwkv6-7b"])
def test_sharded_train_step_matches_single_device(arch):
    """Full train step on a 2x4 (data, model) mesh reproduces the
    single-device loss/grad-norm — validates the entire sharding stack
    (FSDP+TP rules, shard_map MoE, vocab-sharded CE, constraints)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SUBPROCESS_SRC.format(src=os.path.abspath(src), arch=arch)
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout
