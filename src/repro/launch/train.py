"""Production training driver: sharded end-to-end loop with checkpointing.

Assembles mesh → sharded state → jitted train step (the same build path the
dry-run lowers) and actually RUNS it, with:
  * resume-from-latest on start (crash ⇒ relaunch ⇒ identical trajectory,
    because the data pipeline is stateless in the step number);
  * periodic atomic checkpoints;
  * elastic re-mesh: --devices different from the checkpoint's device count
    re-shards on restore (train/checkpoint.py restores through host numpy).

Smoke-scale usage (any host, fake devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --mesh 4,2 --steps 20 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from ..configs import ARCHS
from ..models import build
from ..train import OptimizerConfig, checkpoint as ckpt, init_state, make_train_step
from ..train.data import DataConfig, batch_at, embeds_batch_at
from . import sharding as sh
from .mesh import effective_batch_axes


def make_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split(","))
    names = ("pod", "data", "model")[-len(dims):]
    return jax.make_mesh(dims, names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(dims))


def run(arch: str, mesh_spec: str, steps: int, *, smoke: bool = True,
        seq: int = 64, global_batch: int = 8, microbatches: int = 2,
        ckpt_dir: str | None = None, ckpt_every: int = 50, lr: float = 1e-3,
        log_every: int = 10):
    cfg = ARCHS[arch]
    if smoke:
        cfg = dataclasses.replace(cfg.smoke(), n_layers=2)
    mesh = make_mesh(mesh_spec)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = build(cfg)

    from ..models.layers import set_constraint_mesh
    set_constraint_mesh(mesh)

    state = init_state(model, jax.random.PRNGKey(0))
    specs = sh.state_specs(jax.eval_shape(lambda: state), axis_sizes)
    shardings = sh.named(mesh, specs)
    state = jax.device_put(state, shardings)

    start = 0
    if ckpt_dir and (latest := ckpt.latest_step(ckpt_dir)) is not None:
        state = ckpt.restore(ckpt_dir, latest, state, shardings=shardings)
        start = latest
        print(f"[train] resumed from step {start} (re-sharded onto {mesh_spec})")

    oc = OptimizerConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                         total_steps=steps,
                         schedule="wsd" if cfg.wsd_schedule else "cosine")
    step_fn = jax.jit(
        make_train_step(model, oc, microbatches=microbatches, impl="ref"),
        donate_argnums=(0,))

    baxes = effective_batch_axes(mesh, global_batch)
    dc = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=global_batch,
                    structure=8)
    bspec_fn = lambda b: jax.device_put(
        b, sh.named(mesh, sh.batch_specs(jax.eval_shape(lambda: b), baxes)))

    t0 = time.time()
    metrics = {}
    with mesh:
        for i in range(start, steps):
            if cfg.input_kind == "embeddings" or cfg.family == "encdec":
                batch = embeds_batch_at(dc, i, cfg.d_model)
            else:
                batch = batch_at(dc, i)
            state, metrics = step_fn(state, bspec_fn(batch))
            if i % log_every == 0 or i == steps - 1:
                print(f"[train] step {i:5d} loss {float(metrics['loss']):.4f} "
                      f"lr {float(metrics['lr']):.2e}")
            if ckpt_dir and (i + 1) % ckpt_every == 0:
                ckpt.save(ckpt_dir, i + 1, state)
    if ckpt_dir:
        ckpt.save(ckpt_dir, steps, state)
    dt = time.time() - t0
    print(f"[train] {steps - start} steps in {dt:.1f}s on mesh {mesh_spec} "
          f"({mesh.devices.size} devices); final loss "
          f"{float(metrics['loss']):.4f}")
    return float(metrics["loss"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--mesh", default="4,2")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    run(args.arch, args.mesh, args.steps, smoke=args.smoke, seq=args.seq,
        global_batch=args.batch, microbatches=args.microbatches,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)


if __name__ == "__main__":
    main()
