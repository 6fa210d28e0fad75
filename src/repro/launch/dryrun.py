import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds abstract (ShapeDtypeStruct) parameters, optimizer
state, and inputs with production NamedShardings — no allocation — and runs

    jax.jit(step, in_shardings=..., out_shardings=...).lower(...).compile()

then records memory_analysis() (fits-per-device proof) and cost_analysis()
(FLOPs/bytes for the roofline) plus the collective-byte census parsed from
the optimized HLO. Output: one JSON per cell under launch_out/.

Usage:
    python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--arch ... --shape ...]
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS
from ..configs.base import ShapeCell, shape_cells_for
from ..models import build
from ..train import OptimizerConfig, make_train_step
from ..train.train_step import init_state
from .mesh import effective_batch_axes, make_production_mesh
from . import hlo_cost, roofline
from . import sharding as sh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "launch_out")

# Microbatch count per shape cell: keeps per-µbatch tokens ≈ one sequence
# per data-shard (activation + MoE dispatch memory; see DESIGN.md).
def _microbatches(cell: ShapeCell, data_shards: int) -> int:
    if os.environ.get("REPRO_MICROBATCHES"):        # §Perf H1 knob
        return int(os.environ["REPRO_MICROBATCHES"])
    per_shard = max(cell.global_batch // data_shards, 1)
    return per_shard      # 1 sequence per microbatch per data shard


def _abstract(fn, *args, **kwargs):
    return jax.eval_shape(fn, *args, **kwargs)


def input_specs(cfg, cell: ShapeCell, model):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = cell.global_batch, cell.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if cell.kind == "train":
        batch = {"tokens": tok, "labels": tok}
        if cfg.input_kind == "embeddings":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        return batch
    if cell.kind == "prefill":
        batch = {"tokens": tok}
        if cfg.input_kind == "embeddings":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        return batch
    # decode / long_decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def build_cell(cfg, cell: ShapeCell, mesh):
    """Returns (jitted_fn, example_args_as_SDS) for one cell."""
    model = build(cfg)
    baxes = effective_batch_axes(mesh, cell.global_batch)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_shards = 1
    for a in baxes:
        data_shards *= mesh.shape[a]
    key = jax.random.PRNGKey(0)

    if cell.kind == "train":
        state_shapes = _abstract(lambda: init_state(model, key))
        state_specs = sh.state_specs(state_shapes, axis_sizes)
        state_sds = sh.with_shardings(mesh, state_shapes, state_specs)
        batch_shapes = input_specs(cfg, cell, model)
        bspecs = sh.batch_specs(batch_shapes, baxes)
        batch_sds = sh.with_shardings(mesh, batch_shapes, bspecs)
        oc = OptimizerConfig(total_steps=10_000)
        mb = _microbatches(cell, data_shards)
        step = make_train_step(model, oc, microbatches=mb, impl="chunked",
                               remat=True)
        fn = jax.jit(step, donate_argnums=(0,))
        return fn, (state_sds, batch_sds)

    params_shapes = _abstract(model.init, key)
    pspecs = sh.param_specs(params_shapes, axis_sizes)
    params_sds = sh.with_shardings(mesh, params_shapes, pspecs)

    if cell.kind == "prefill":
        batch_shapes = input_specs(cfg, cell, model)
        bspecs = sh.batch_specs(batch_shapes, baxes)
        batch_sds = sh.with_shardings(mesh, batch_shapes, bspecs)

        def prefill(params, batch):
            logits, _ = model.forward(params, batch, impl="chunked",
                                      remat=True, last_only=True)
            return logits
        return jax.jit(prefill), (params_sds, batch_sds)

    # decode / long_decode: serve_step(params, tok, cache, pos)
    b, s = cell.global_batch, cell.seq_len
    if cfg.family == "encdec":
        cache_shapes = _abstract(lambda: model.init_cache(b, s, s))
    else:
        cache_shapes = _abstract(lambda: model.init_cache(b, s))
    cspecs = sh.cache_specs(cache_shapes, baxes, axis_sizes)
    cache_sds = sh.with_shardings(mesh, cache_shapes, cspecs)
    tok_sds = sh.with_shardings(
        mesh, {"t": jax.ShapeDtypeStruct((b, 1), jnp.int32)},
        {"t": jax.sharding.PartitionSpec(baxes if baxes else None, None)})["t"]
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(params, tok, cache, pos):
        return model.decode_step(params, tok, cache, pos)
    return jax.jit(serve_step, donate_argnums=(2,)), \
        (params_sds, tok_sds, cache_sds, pos)


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str = OUT_DIR):
    cfg = ARCHS[arch]
    cell = next(c for c in shape_cells_for(cfg) if c.name == shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    from ..models.layers import set_constraint_mesh
    set_constraint_mesh(mesh)
    fn, args = build_cell(cfg, cell, mesh)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # loop-aware per-device census from the optimized HLO (hlo_cost.py):
    # cost_analysis() counts while bodies once and is kept as a cross-check.
    census = hlo_cost.analyze(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "kind": cell.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": census["flops"],                  # per device, loop-aware
        "dot_bytes": census["dot_bytes"],
        "collective_bytes": census["collective_bytes"],
        "unknown_trip_bodies": census["unknown_trip_bodies"],
        "xla_cost_flops_bodies_once": cost.get("flops", 0.0),
        "xla_bytes_accessed_bodies_once": cost.get("bytes accessed", 0.0),
        "memory": {
            k: getattr(mem, k, None) for k in
            ("temp_size_in_bytes", "argument_size_in_bytes",
             "output_size_in_bytes", "generated_code_size_in_bytes")
        },
        "num_devices": mesh.devices.size,
    }
    rec["roofline"] = roofline.terms(rec)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    r = rec["roofline"]
    print(f"[dryrun] {arch} × {shape} × {mesh_name}: compile {t_compile:.0f}s | "
          f"flops/dev {rec['flops']:.3e} | "
          f"args/dev {(rec['memory']['argument_size_in_bytes'] or 0)/2**30:.2f} GiB | "
          f"temp/dev {(rec['memory']['temp_size_in_bytes'] or 0)/2**30:.2f} GiB | "
          f"coll/dev {rec['collective_bytes']/2**30:.3f} GiB | "
          f"bottleneck {r['bottleneck']} ({r['step_lower_bound_s']*1e3:.1f} ms)")
    print(f"  memory_analysis: {mem}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args(argv)

    cells = []
    for arch, cfg in ARCHS.items():
        if args.arch and arch != args.arch:
            continue
        for cell in shape_cells_for(cfg):
            if args.shape and cell.name != args.shape:
                continue
            cells.append((arch, cell.name))
    if not args.all and len(cells) > 1 and not (args.arch and args.shape):
        pass  # allow suites via --all or filters
    ok = fail = 0
    for arch, shape in cells:
        try:
            run_cell(arch, shape, args.multi_pod, args.out)
            ok += 1
        except Exception:
            fail += 1
            print(f"[dryrun] FAIL {arch} × {shape}", file=sys.stderr)
            traceback.print_exc()
    print(f"[dryrun] done: {ok} ok, {fail} failed")
    return 1 if fail else 0


if __name__ == "__main__":
    sys.exit(main())
