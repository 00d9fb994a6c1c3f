"""Fault-tolerant training driver.

Wires together: model plane (any assigned arch), synthetic data pipeline,
AdamW, GSPMD sharding on the ambient mesh, and the paper's plane —
erasure-coded checkpoints with JLCM-planned placement. Demonstrates:

  * periodic EC checkpointing (any n-k node losses survivable),
  * crash/restart recovery (seekable data pipeline resumes exactly),
  * storage-node failure injection mid-run + elastic replan,
  * optional int8 gradient compression with error feedback.

CPU-runnable with reduced configs (examples/train_lm.py); the same driver
lowers on the production mesh via launch/dryrun.py.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ECCheckpointStore, plan_for_params
from repro.configs.registry import get_config, get_smoke_config
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import TrainState, build_model, jit_train_step
from repro.optim import AdamW, compress_decompress, compress_init, cosine_schedule
from repro.storage import tahoe_testbed


def train(
    arch: str = "smollm-135m",
    *,
    smoke: bool = True,
    steps: int = 200,
    batch: int = 8,
    seq: int = 64,
    lr: float = 3e-3,
    ckpt_every: int = 50,
    ckpt_dir: str | None = None,
    fail_node_at: int | None = None,
    grad_compress: bool = False,
    resume: bool = False,
    log_every: int = 10,
    dtype=jnp.float32,
):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    mesh = make_local_mesh()
    model = build_model(cfg, mesh, dtype=dtype, remat="none")
    opt = AdamW(lr=cosine_schedule(lr, warmup=20, total=steps), weight_decay=0.01)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq, global_batch=batch)

    batch_sds = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    with jax.set_mesh(mesh):
        step_fn, abstract, state_sh, batch_sh = jit_train_step(model, opt, mesh, batch_sds)

        params = model.init(jax.random.key(0))
        state = jax.device_put(
            TrainState(params=params, opt=opt.init(params)), state_sh
        )
        cstate = compress_init(params) if grad_compress else None

        # --- paper plane: EC checkpoint store on the 3-site testbed model
        store = None
        start_step = 0
        if ckpt_dir:
            cluster = tahoe_testbed()
            # plan over the FULL train state (params + optimizer moments)
            plan = plan_for_params(
                state, cluster, group_mb=4.0, chunk_mb=1.0, theta=0.5
            )
            store = ECCheckpointStore(ckpt_dir, plan)
            print(
                f"[train] EC checkpoint plan: {len(plan.groups)} groups, "
                f"restore-latency bound {plan.latency_bound:.1f}s, "
                f"storage cost ${plan.storage_cost:.0f}"
            )
            latest = sorted(
                int(p.stem.split("_")[1]) for p in Path(ckpt_dir).glob("manifest_*.json")
            )
            if resume and latest:
                start_step = latest[-1]
                print(f"[train] restoring step {start_step} from EC store")
                state = store.restore(start_step, state)

        losses = []
        t0 = time.time()
        for step in range(start_step, steps):
            b = jax.device_put(data.batch_at(step), batch_sh)
            if grad_compress:
                # EF-compressed gradient path (wire-format modelled)
                loss, grads = jax.value_and_grad(model.loss)(state.params, b)
                grads, cstate = compress_decompress(grads, cstate)
                new_params, new_opt = opt.update(grads, state.opt, state.params)
                state = TrainState(new_params, new_opt)
                metrics = {"loss": loss}
            else:
                state, metrics = step_fn(state, b)
            losses.append(float(metrics["loss"]))
            if step % log_every == 0:
                print(f"[train] step {step:4d} loss {losses[-1]:.4f}")
            if store and step and step % ckpt_every == 0:
                store.save(state, step)
                print(f"[train] EC checkpoint @ step {step}")
            if store and fail_node_at is not None and step == fail_node_at:
                victim = store.plan.groups[0].placement[0]
                store.fail_node(victim)
                print(f"[train] !! injected failure of storage node {victim}")
        wall = time.time() - t0
        print(
            f"[train] done: {steps - start_step} steps in {wall:.1f}s; "
            f"loss {losses[0]:.3f} -> {losses[-1]:.3f}"
        )
        return state, losses, store


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-node-at", type=int, default=None)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    train(
        args.arch,
        smoke=not args.full,
        steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        lr=args.lr,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        fail_node_at=args.fail_node_at,
        grad_compress=args.grad_compress,
        resume=args.resume,
    )


if __name__ == "__main__":
    main()
