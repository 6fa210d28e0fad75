"""End-to-end LM training on the substrate the dry-run deploys: a reduced
minicpm-style model (WSD schedule, the arch's paper-of-record trick), with
checkpoint/restart fault tolerance demonstrated mid-run.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses
import tempfile
import time

import jax

from repro.configs import ARCHS
from repro.models import build
from repro.train import (OptimizerConfig, checkpoint as ckpt, init_state,
                         make_train_step)
from repro.train.data import DataConfig, batch_at
from repro.xla_cache import use_persistent_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    args = ap.parse_args()

    # reduced same-family config, slightly widened for a real loss curve
    cfg = dataclasses.replace(ARCHS[args.arch].smoke(), n_layers=4, vocab=1024)
    model = build(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(model.init(jax.random.PRNGKey(0))))
    print(f"arch={cfg.name} (reduced) params={n_params/1e6:.1f}M "
          f"schedule={'wsd' if cfg.wsd_schedule else 'cosine'}")

    oc = OptimizerConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps,
                         schedule="wsd" if cfg.wsd_schedule else "cosine")
    step_fn = jax.jit(make_train_step(model, oc,
                                      microbatches=args.microbatches, impl="ref"))
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
                    structure=8)

    state = init_state(model, jax.random.PRNGKey(0))
    ckpt_dir = tempfile.mkdtemp(prefix="repro_ckpt_")
    t0 = time.time()
    for i in range(args.steps):
        state, m = step_fn(state, batch_at(dc, i))
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}  gnorm {float(m['grad_norm']):.2f}")
        if i == args.steps // 2:
            # mid-run checkpoint + simulated failure + restore
            ckpt.save(ckpt_dir, i + 1, state)
            print(f"--- checkpoint at step {i+1}; simulating failure+restart ---")
            state = ckpt.restore(ckpt_dir, ckpt.latest_step(ckpt_dir),
                                 init_state(model, jax.random.PRNGKey(0)))
    dt = time.time() - t0
    toks = args.steps * args.batch * args.seq
    print(f"done: {args.steps} steps, {toks/dt:.0f} tok/s on CPU, "
          f"final loss {float(m['loss']):.4f}")


if __name__ == "__main__":
    use_persistent_cache()
    main()
