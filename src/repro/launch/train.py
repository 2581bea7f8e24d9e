"""Training driver with ThinkAir fleet integration.

Runnable at laptop scale (``--reduced``) and lowerable at production scale.
Fleet features (DESIGN.md §8):
 - checkpoint/restart (atomic, async, step-versioned);
 - elastic data-parallel scaling through the ThinkAir clone pool (resizes
   between steps; provisioning charged like the paper's VM resumes);
 - fault injection -> restore-from-checkpoint restart path;
 - optional manual-collective DP with int8+error-feedback gradient
   compression (shard_map path, used when the mesh has >1 data shard).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config, reduced_config
from repro.core.clones import ClonePool
from repro.core.faults import FaultPlan
from repro.data.pipeline import DataConfig, Pipeline
from repro.distributed import compression
from repro.launch import steps as S
from repro.models import model
from repro.optim import adamw
from repro.optim.adamw import OptConfig


def build_compressed_train_step(cfg, opt_cfg, ctx):
    """Manual-DP: per-shard grads, int8+EF all-reduce over 'data'."""
    from jax.sharding import PartitionSpec as P

    def step_fn(state: Dict, batch: Dict):
        def local_step(params, opt, ef, local_batch):
            def loss_fn(p):
                local_ctx = dataclasses.replace(ctx, mesh=None)
                return model.forward(cfg, p, local_batch, local_ctx, "train")

            (total, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads, ef = compression.tree_compressed_pmean(grads, ef, "data")
            new_params, new_opt, om = adamw.update(opt_cfg, grads, opt,
                                                   params)
            metrics = {**metrics, **om, "total": total}
            metrics = jax.tree.map(lambda x: jax.lax.pmean(x, "data"),
                                   metrics)
            return new_params, new_opt, ef, metrics

        new_p, new_o, new_ef, metrics = jax.shard_map(
            local_step, mesh=ctx.mesh,
            in_specs=(P(), P(), P(), P("data")),
            out_specs=(P(), P(), P(), P()), check_vma=False,
        )(state["params"], state["opt"], state["ef"], batch)
        return {"params": new_p, "opt": new_o, "ef": new_ef}, metrics

    return step_fn


@dataclasses.dataclass
class TrainReport:
    steps_done: int = 0
    restarts: int = 0
    resizes: int = 0
    provision_seconds: float = 0.0
    losses: list = dataclasses.field(default_factory=list)


class FleetTrainer:
    """Elastic, fault-tolerant training loop driven by the ThinkAir pool."""

    def __init__(self, cfg, *, steps_total: int, data_cfg: DataConfig,
                 opt_cfg: OptConfig = OptConfig(), ckpt_dir: str = None,
                 ckpt_every: int = 20, fault_plan: Optional[FaultPlan] = None,
                 grad_compression: bool = False, mesh=None,
                 elastic_schedule: Optional[dict] = None):
        self.cfg = cfg
        self.steps_total = steps_total
        self.opt_cfg = opt_cfg
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.pipe = Pipeline(cfg, data_cfg)
        self.faults = fault_plan or FaultPlan()
        self.pool = ClonePool(link_name="dcn", tpu=True)
        self.elastic_schedule = elastic_schedule or {}
        self.report = TrainReport()
        self.mesh = mesh
        self.ctx = S.make_context(mesh)
        if grad_compression and mesh is not None \
                and mesh.shape.get("data", 1) > 1:
            self._build = lambda: build_compressed_train_step(
                cfg, opt_cfg, self.ctx)
            self._compressed = True
        else:
            self._build = lambda: S.build_train_step(cfg, opt_cfg, self.ctx)
            self._compressed = False
        self.step_fn = jax.jit(self._build())

    def init_state(self, seed: int = 0) -> Dict:
        params = model.init(self.cfg, jax.random.PRNGKey(seed))
        state = {"params": params, "opt": adamw.init(params)}
        if self._compressed:
            state["ef"] = compression.init_error_feedback(params)
        return state

    def run(self, state: Optional[Dict] = None) -> Dict:
        start = 0
        if state is None:
            state = self.init_state()
            if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) is not None:
                start, state = ckpt.restore(self.ckpt_dir, state)
                self.report.restarts += 1
        i = start
        while i < self.steps_total:
            if i in self.elastic_schedule:
                # elastic resize: provision clones; cost accounted like the
                # paper's VM resume/boot
                n = self.elastic_schedule[i]
                _, cost = self.pool.acquire("main", n=n)
                self.report.provision_seconds += cost
                self.report.resizes += 1
            batch = self.pipe.batch(i)
            if self.faults.check():
                # node failure mid-step: restart from latest checkpoint
                self.report.restarts += 1
                if self.ckpt_dir and ckpt.latest_step(self.ckpt_dir) \
                        is not None:
                    i, state = ckpt.restore(self.ckpt_dir, state)
                continue
            state, metrics = self.step_fn(state, batch)
            loss = float(metrics["loss"])
            self.report.losses.append(loss)
            i += 1
            self.report.steps_done += 1
            if self.ckpt_dir and i % self.ckpt_every == 0:
                ckpt.save(self.ckpt_dir, i, state)
        if self.ckpt_dir:
            ckpt.save(self.ckpt_dir, i, state)
        return state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    trainer = FleetTrainer(
        cfg, steps_total=args.steps,
        data_cfg=DataConfig(args.batch, args.seq),
        ckpt_dir=args.ckpt_dir)
    t0 = time.time()
    state = trainer.init_state()
    for i in range(args.steps):
        batch = trainer.pipe.batch(i)
        state, metrics = trainer.step_fn(state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:5d} loss={float(metrics['loss']):.4f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({time.time() - t0:.1f}s)")
    print("done")


if __name__ == "__main__":
    main()
