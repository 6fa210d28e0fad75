"""Roofline machinery: loop-aware HLO cost parser vs known-flop programs;
sharding spec rules; xla cost_analysis undercount documented."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze
from repro.launch import roofline
from repro.launch.sharding import param_specs
from jax.sharding import PartitionSpec as P


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_single_matmul_flops():
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    res = analyze(_compile(lambda a, b: a @ b, x, w).as_text())
    assert res["flops"] == 2 * 64 * 128 * 256


def test_scan_trip_count_multiplies():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]
    res = analyze(_compile(scanned, x, ws).as_text())
    assert res["flops"] == 2 * 128 ** 3 * 10
    assert not res["unknown_trip_bodies"]


def test_nested_loops_multiply():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def nested(x, ws):
        def outer(i, acc):
            return jax.lax.scan(lambda c, w: (c @ w, None), acc, ws)[0]
        return jax.lax.fori_loop(0, 5, outer, x)
    res = analyze(_compile(nested, x, ws).as_text())
    assert res["flops"] == 2 * 128 ** 3 * 10 * 5


def test_xla_cost_analysis_counts_bodies_once():
    """The reason hlo_cost.py exists (documented undercount)."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)

    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]
    comp = _compile(scanned, x, ws)
    assert comp.cost_analysis()["flops"] < 2 * 128 ** 3 * 2   # ~1 body


def test_data_dependent_while_flagged():
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)

    def fixpoint(x):
        def cond(s):
            return jnp.max(s) > 1e-3
        return jax.lax.while_loop(cond, lambda s: (s @ s) * 0.5, x)
    res = analyze(_compile(fixpoint, x).as_text())
    assert res["unknown_trip_bodies"]          # honest: trips unknowable


def test_roofline_terms_and_bottleneck():
    rec = {"flops": 1.97e14, "dot_bytes": 8.19e11, "collective_bytes": 1.5e11,
           "num_devices": 256}
    t = roofline.terms(rec)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)
    rec["flops"] = 4e14
    assert roofline.terms(rec)["bottleneck"] == "compute"


def test_param_sharding_rules():
    from repro.configs import ARCHS
    from repro.models import build
    cfg = ARCHS["qwen2.5-3b"]
    m = build(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    specs = param_specs(shapes, {"data": 16, "model": 16})
    assert specs["embed"] == P("model", "data")
    assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")
    assert specs["layers"]["mlp"]["w_down"] == P(None, "model", "data")
    assert specs["ln_f"]["scale"] == P(None)
    # kv projection output (2 heads × 128 = 256) still divides 16 → sharded
    assert specs["layers"]["attn"]["wk"] == P(None, "data", "model")


def test_divisibility_guard():
    from repro.configs import ARCHS
    from repro.models import build
    cfg = ARCHS["xlstm-1.3b"]
    m = build(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    specs = param_specs(shapes, {"data": 16, "model": 16})
    # wf: [d, 4 heads] — 4 % 16 != 0 → second dim replicated
    assert specs["mlstm"]["wf"] == P(None, "data", None)


def test_model_flops_analytic():
    from repro.configs import ARCHS
    cfg = ARCHS["qwen2.5-3b"]
    n = roofline.param_count(cfg)
    assert 2.5e9 < n < 4.0e9            # ~3B params
    moe = ARCHS["qwen3-moe-235b-a22b"]
    assert 180e9 < roofline.param_count(moe) < 280e9
    active = roofline.param_count(moe, active_only=True)
    assert 15e9 < active < 30e9         # ~22B active
