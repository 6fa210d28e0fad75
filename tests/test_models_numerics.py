"""Model-layer numerics: chunked vs sequential linear attention, chunked vs
ref attention, train/decode consistency, checkpoint elastic restore."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import build
from repro.models.attention import chunked_attention
from repro.models.ssm import (chunked_linear_attention, linear_attention_ref)
from repro.kernels.flash_attention.ref import attention_ref


@pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (128, 32)])
def test_chunked_linear_attention(s, chunk):
    rng = np.random.default_rng(s)
    b, h, n, p = 2, 3, 8, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32) * 0.3
    v = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    la = jnp.asarray(-np.abs(rng.normal(size=(b, s, h))) * 0.5, jnp.float32)
    got = chunked_linear_attention(q, k, v, la, chunk)
    ref = linear_attention_ref(q, k, v, la)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("sq,skv", [(128, 128), (128, 256), (256, 256)])
def test_chunked_attention_matches_ref(sq, skv):
    rng = np.random.default_rng(sq)
    b, h, d = 2, 4, 32
    q = jnp.asarray(rng.normal(size=(b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, skv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, skv, d)), jnp.float32)
    got = chunked_attention(q, k, v, causal=True, q_chunk=64, k_chunk=64)
    ref = attention_ref(q.reshape(b * h, sq, d), k.reshape(b * h, skv, d),
                        v.reshape(b * h, skv, d), causal=True)
    np.testing.assert_allclose(np.asarray(got).reshape(b * h, sq, d),
                               np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("name", ["qwen2.5-3b", "deepseek-moe-16b", "xlstm-1.3b"])
def test_train_decode_consistency(name):
    """Teacher-forced forward's last-token logits ≈ decode-chain logits.
    MoE: capacity dropping is T-dependent by design, so the consistency
    check runs with a capacity factor large enough that nothing drops."""
    import dataclasses
    cfg = ARCHS[name].smoke()
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=16.0)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
    lf, _ = m.forward(params, {"tokens": toks}, impl="ref", remat=False)
    cache = m.init_cache(1, 8)
    ld = None
    for i in range(8):
        ld, cache = m.decode_step(params, toks[:, i:i + 1], cache, jnp.int32(i))
    err = float(jnp.max(jnp.abs(lf[0, -1] - ld[0])))
    assert err < 0.05, err          # bf16 accumulation tolerance


def test_elastic_checkpoint_restore_other_mesh():
    """Save unsharded, restore with explicit single-device shardings — the
    re-mesh path restores through host numpy + device_put."""
    import tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import checkpoint as ckpt, init_state

    cfg = ARCHS["qwen2.5-3b"].smoke()
    m = build(cfg)
    state = init_state(m, jax.random.PRNGKey(0))
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 0, state)
        restored = ckpt.restore(d, 0, state, shardings=shardings)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))


def test_moe_capacity_drops_gracefully():
    """With a tiny capacity factor the MoE layer still runs and routes a
    subset of tokens (overflow dropped, never NaN)."""
    import dataclasses
    cfg = dataclasses.replace(ARCHS["deepseek-moe-16b"].smoke(),
                              moe_capacity_factor=0.25)
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    logits, aux = m.forward(params, {"tokens": jnp.ones((2, 16), jnp.int32)},
                            impl="ref", remat=False)
    assert bool(jnp.isfinite(logits).all()) and bool(jnp.isfinite(aux))


def test_serve_engine_generates():
    from repro.serve import ServeEngine
    cfg = ARCHS["qwen2.5-3b"].smoke()
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, params, max_len=32, batch_size=2)
    prompts = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    res = eng.generate(prompts, new_tokens=6)
    assert res.tokens.shape == (2, 10)
    assert np.array_equal(res.tokens[:, :4], prompts)
