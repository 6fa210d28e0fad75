"""Batched serving demo: train a tiny model briefly so generation is
non-degenerate, then serve batched greedy continuations through the same
decode_step the dry-run lowers at decode_32k/long_500k shapes.

    PYTHONPATH=src python examples/serve_lm.py
"""
import dataclasses

import jax
import numpy as np

from repro.configs import ARCHS
from repro.models import build
from repro.serve import ServeEngine
from repro.train import OptimizerConfig, init_state, make_train_step
from repro.train.data import DataConfig, batch_at
from repro.xla_cache import use_persistent_cache


def main():
    cfg = dataclasses.replace(ARCHS["qwen2.5-3b"].smoke(), n_layers=2, vocab=256)
    model = build(cfg)
    state = init_state(model, jax.random.PRNGKey(0))
    oc = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=60)
    step = jax.jit(make_train_step(model, oc, impl="ref"))
    dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, structure=4)
    for i in range(60):
        state, m = step(state, batch_at(dc, i))
    print(f"pre-trained tiny model to loss {float(m['loss']):.3f} "
          "(periodic n-grams)")

    engine = ServeEngine(model, state.params, max_len=48, batch_size=4)
    # prompts drawn from the training distribution (period-4 n-grams)
    base = batch_at(dc, 999)["tokens"][:4, :8]
    res = engine.generate(np.asarray(base), new_tokens=12)
    for i, seq in enumerate(res.tokens):
        prompt, gen = seq[:8].tolist(), seq[8:].tolist()
        print(f"req{i}: prompt={prompt} → generated={gen}")
    # a learned period-4 model should repeat the prompt's cycle
    period_hits = sum(int(seq[8 + j] == seq[8 + j - 4])
                      for seq in res.tokens for j in range(4, 12))
    print(f"period-4 consistency: {period_hits}/{4*8} generated tokens")


if __name__ == "__main__":
    use_persistent_cache()
    main()
