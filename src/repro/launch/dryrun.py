"""Multi-pod dry-run: lower + compile every (architecture x shape) cell on the
production mesh and record memory / cost / collective analyses.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both] [--force]

Per cell this builds the real step function:
  train_4k           -> train_step (fwd + bwd + AdamW, microbatched per config)
  prefill_32k        -> serve_step = model.prefill (cache build)
  decode_32k/long_500k -> serve_step = model.decode_step (1 token vs cache)

with in/out shardings from ``repro.parallel.sharding`` and inputs as
ShapeDtypeStructs (zero allocation).  Results are cached incrementally in
results/dryrun/<cell>.json; reduced-depth (L=1, L=2) variants are also
compiled for the roofline's scan-trip-count correction (DESIGN.md §6).

It compiles for 512 virtual CPU devices, which :func:`main` pins before the
process's first device query (importing this module sets nothing).
"""

import argparse
import dataclasses
import json
import os
import time
import traceback

import zstandard

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.configs.base import ModelConfig, OptimizerConfig, ShapeConfig
from repro.core.compat import set_mesh
from repro.core.hlo_analysis import analyze_hlo
from repro.launch.mesh import data_axes_of, make_production_mesh
from repro.models import batch_spec, build_model
from repro.parallel import sharding as shd
from repro.parallel.context import ParallelContext
from repro.train.optimizer import init_opt_state
from repro.train.train_loop import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------


def make_context(cfg: ModelConfig, shape: ShapeConfig, mesh,
                 overrides: dict | None = None) -> ParallelContext:
    o = overrides or {}
    return ParallelContext(
        mesh=mesh,
        data_axes=data_axes_of(mesh),
        model_axis="model",
        seq_parallel=o.get(
            "seq_parallel",
            shape.kind == "prefill" and cfg.partitioned_collectives
            and cfg.family in ("dense", "moe", "vlm", "audio")),
        moe_mode=o.get("moe_mode", "ep" if cfg.family == "moe" else "dense"),
        n_parts=o.get("n_parts", cfg.halo_n_parts
                      if cfg.partitioned_collectives else 1),
        state_method=o.get("state_method", "ring"),
        tp_mode=o.get("tp_mode", "gspmd"),
    )


def _microbatches(cfg: ModelConfig, shape: ShapeConfig, mesh) -> int:
    if shape.kind != "train" or cfg.train_microbatches <= 1:
        return 1
    dsize = int(np.prod([mesh.shape[a] for a in data_axes_of(mesh)]))
    mb = min(cfg.train_microbatches, max(1, shape.global_batch // dsize))
    while shape.global_batch % mb or (shape.global_batch // mb) % dsize:
        mb -= 1
    return max(1, mb)


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               overrides: dict | None = None):
    """Returns (step_fn, abstract_args, in_shardings, donate_argnums)."""
    model = build_model(cfg)
    ctx = make_context(cfg, shape, mesh, overrides)
    da = ctx.data_axes
    msize = mesh.shape["model"]

    if shape.kind == "train":
        opt_cfg = OptimizerConfig()
        mb = _microbatches(cfg, shape, mesh)
        step = make_train_step(model, opt_cfg, ctx, microbatches=mb)
        params_sh = model.init_shape()
        state_sh = {"params": params_sh,
                    "opt": jax.eval_shape(
                        lambda: init_opt_state(params_sh, opt_cfg,
                                               cfg.opt_state_dtype))}
        pkw = dict(model_axis="model", model_size=msize,
                   fsdp_experts=cfg.fsdp_experts, data_axes=da, mesh=mesh)
        pspec = shd.param_pspecs(params_sh, **pkw)
        mspec = shd.zero1_pspecs(
            state_sh["opt"]["m"],
            shd.param_pspecs(state_sh["opt"]["m"], **pkw),
            data_axes=da, mesh=mesh)
        state_spec = {"params": pspec,
                      "opt": {"m": mspec, "v": mspec, "step": P()}}
        bspec_tree = batch_spec(cfg, shape)
        bspec = shd.batch_pspecs(bspec_tree, data_axes=da, mesh=mesh)
        args = (
            shd.shaped_with_sharding(state_sh, mesh, state_spec),
            shd.shaped_with_sharding(bspec_tree, mesh, bspec),
        )
        return step, args, (0,)

    model_obj = model
    if shape.kind == "prefill" and cfg.is_encoder_only:
        # encoder-only: the inference-prefill cell is a full encode pass
        bspec_tree = batch_spec(cfg, shape)
        bspec_tree.pop("labels", None)
        bspec_tree.pop("mask", None)
        params_sh = model.init_shape()
        pspec = shd.param_pspecs(params_sh, model_axis="model",
                                 model_size=msize,
                                 fsdp_experts=cfg.fsdp_experts,
                                 data_axes=da, mesh=mesh)
        bspec = shd.batch_pspecs(bspec_tree, data_axes=da, mesh=mesh)

        def encode_step(params, batch):
            return model_obj.logits(params, batch, ctx=ctx)

        args = (
            shd.shaped_with_sharding(params_sh, mesh, pspec),
            shd.shaped_with_sharding(bspec_tree, mesh, bspec),
        )
        return encode_step, args, ()

    if shape.kind == "prefill":
        bspec_tree = batch_spec(cfg, shape)
        cache_sh = model.cache_spec(shape.global_batch, shape.seq_len)

        def serve_step(params, batch, cache):
            return model_obj.prefill(params, batch, cache, ctx=ctx)

    else:  # decode
        bspec_tree = {"tokens": jax.ShapeDtypeStruct(
            (shape.global_batch, 1), jnp.int32)}
        cache_sh = model.cache_spec(shape.global_batch, shape.seq_len)

        def serve_step(params, batch, cache):
            return model_obj.decode_step(params, batch["tokens"], cache,
                                         ctx=ctx)

    params_sh = model.init_shape()
    pspec = shd.param_pspecs(params_sh, model_axis="model", model_size=msize,
                             fsdp_experts=cfg.fsdp_experts, data_axes=da,
                             mesh=mesh)
    bspec = shd.batch_pspecs(bspec_tree, data_axes=da, mesh=mesh)
    cspec = shd.cache_pspecs(cache_sh, data_axes=da, model_axis="model",
                             model_size=msize, mesh=mesh)
    args = (
        shd.shaped_with_sharding(params_sh, mesh, pspec),
        shd.shaped_with_sharding(bspec_tree, mesh, bspec),
        shd.shaped_with_sharding(cache_sh, mesh, cspec),
    )
    return serve_step, args, (2,)


# ---------------------------------------------------------------------------
# depth-reduced variants (roofline trip-count correction)
# ---------------------------------------------------------------------------


def reduced_depth(cfg: ModelConfig, units: int) -> tuple[ModelConfig, int]:
    """A config with ``units`` scan iterations; returns (cfg, full_units)."""
    if cfg.family == "hybrid":
        g = cfg.attn_every
        full = cfg.n_layers // g  # groups (tail ~ scaled by analyzer)
        return cfg.with_updates(n_layers=units * g), full
    if cfg.family == "vlm":
        per = cfg.n_layers // cfg.n_cross_layers
        full = cfg.n_cross_layers
        return cfg.with_updates(n_layers=units * per, n_cross_layers=units), full
    return cfg.with_updates(n_layers=units), cfg.n_layers


# ---------------------------------------------------------------------------
# run one cell
# ---------------------------------------------------------------------------


def _save_hlo(text: str, path: str) -> None:
    with open(path, "wb") as f:
        f.write(zstandard.ZstdCompressor(level=6).compress(text.encode()))


def _load_hlo(path: str) -> str:
    with open(path, "rb") as f:
        return zstandard.ZstdDecompressor().decompress(f.read()).decode()


def _stats_dict(text: str, trip_default: int) -> dict:
    stats = analyze_hlo(text, default_group=1, default_trip=trip_default)
    return {
        "flops": stats.flops,
        "bytes": stats.bytes,
        "wire_bytes": stats.wire_bytes,
        "wire_by_op": {k: float(v) for k, v in stats.by_op_bytes.items()},
        "coll_counts": dict(stats.by_op_counts),
        "n_loops": stats.n_loops,
        "trip_counts": stats.trip_counts[:64],
    }


def _analyze(compiled, cfg: ModelConfig, trip_default: int) -> dict:
    ma = compiled.memory_analysis()
    from repro.core.compat import cost_analysis_dict

    ca = cost_analysis_dict(compiled)
    text = compiled.as_text()
    stats = analyze_hlo(text, default_group=1, default_trip=trip_default)
    return {
        # loop-aware totals (DESIGN.md §6); xla_* are the raw cost_analysis
        # numbers (loop bodies counted once) kept for cross-reference.
        "flops": stats.flops,
        "bytes": stats.bytes,
        "xla_flops": float(ca.get("flops", 0.0)),
        "xla_bytes": float(ca.get("bytes accessed", 0.0)),
        "wire_bytes": stats.wire_bytes,
        "wire_by_op": {k: float(v) for k, v in stats.by_op_bytes.items()},
        "coll_counts": dict(stats.by_op_counts),
        "n_loops": stats.n_loops,
        "trip_counts": stats.trip_counts[:64],
        "memory": {
            "argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "peak": ma.peak_memory_in_bytes,
            "alias": ma.alias_size_in_bytes,
        },
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None, depth_variants: bool = False,
             tag: str = "") -> dict:
    cfg = get_config(arch)
    # "cfg.<field>=<val>" overrides patch the model config (perf experiments)
    if overrides:
        patches = {k[4:]: v for k, v in overrides.items()
                   if k.startswith("cfg.")}
        if patches:
            cfg = cfg.with_updates(**patches)
        overrides = {k: v for k, v in overrides.items()
                     if not k.startswith("cfg.")}
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    result: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": mesh.size,
        "overrides": overrides or {},
        "microbatches": _microbatches(cfg, shape, mesh),
    }
    t0 = time.time()
    step, args, donate = build_cell(cfg, shape, mesh, overrides)
    with set_mesh(mesh):
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
    result["compile_s"] = round(time.time() - t0, 1)
    trip = reduced_depth(cfg, 1)[1]
    result["full"] = _analyze(compiled, cfg, trip)
    hlo_path = cell_path(arch, shape_name, multi_pod, tag) + ".hlo.zst"
    _save_hlo(compiled.as_text(), hlo_path)
    hbm = 16e9
    need = result["full"]["memory"]["peak"] or (
        result["full"]["memory"]["argument"] + result["full"]["memory"]["temp"]
        + result["full"]["memory"]["output"])
    result["fits_16gb"] = bool(need <= hbm)
    del compiled, lowered

    if depth_variants:
        # L=1 / L=2 compiles for the scan flop/byte correction
        for units in (1, 2):
            cfg_u, full_units = reduced_depth(cfg, units)
            step_u, args_u, donate_u = build_cell(cfg_u, shape, mesh, overrides)
            with set_mesh(mesh):
                comp_u = jax.jit(step_u, donate_argnums=donate_u).lower(
                    *args_u).compile()
            result[f"depth{units}"] = _analyze(comp_u, cfg_u, units)
            del comp_u
        result["scan_units_full"] = reduced_depth(cfg, 1)[1]
    return result


def cell_path(arch: str, shape_name: str, multi_pod: bool, tag: str = "") -> str:
    mesh = "multi" if multi_pod else "single"
    suffix = f".{tag}" if tag else ""
    return os.path.join(RESULTS_DIR, f"{arch}.{shape_name}.{mesh}{suffix}.json")


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in cfg.shapes():
            cells.append((arch, shape.name))
    return cells


def main() -> None:
    # a CPU rehearsal of the production mesh: XLA reads the device count at
    # backend start-up, which has not happened yet
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--depth-variants", action="store_true",
                    help="also compile L=1/L=2 variants (debug cross-check)")
    ap.add_argument("--tag", default="", help="result-file suffix for perf "
                    "experiments (e.g. hillclimb variants)")
    ap.add_argument("--set", action="append", default=[],
                    help="context override k=v (seq_parallel, n_parts, "
                    "moe_mode, state_method)")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute analysis from stored HLO (no compile)")
    args = ap.parse_args()

    if args.reanalyze:
        import glob as _glob

        for jpath in sorted(_glob.glob(os.path.join(RESULTS_DIR, "*.json"))):
            hpath = jpath + ".hlo.zst"
            if not os.path.exists(hpath):
                continue
            with open(jpath) as f:
                res = json.load(f)
            cfg = get_config(res["arch"])
            trip = reduced_depth(cfg, 1)[1]
            res["full"].update(_stats_dict(_load_hlo(hpath), trip))
            with open(jpath, "w") as f:
                json.dump(res, f, indent=1)
            print(f"reanalyzed {os.path.basename(jpath)}", flush=True)
        return

    overrides: dict = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = (v == "true" if v in ("true", "false") else
                        int(v) if v.isdigit() else v)

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    os.makedirs(RESULTS_DIR, exist_ok=True)

    ok = fail = skip = 0
    for arch, shape_name in cells:
        for mesh_kind in meshes:
            multi = mesh_kind == "multi"
            path = cell_path(arch, shape_name, multi, args.tag)
            if os.path.exists(path) and not args.force:
                skip += 1
                continue
            label = f"{arch} x {shape_name} x {mesh_kind}"
            try:
                res = run_cell(arch, shape_name, multi, overrides or None,
                               depth_variants=args.depth_variants and not multi,
                               tag=args.tag)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                m = res["full"]["memory"]
                print(f"PASS {label}: compile={res['compile_s']}s "
                      f"peak={m['peak']/1e9:.2f}GB args={m['argument']/1e9:.2f}GB "
                      f"fits={res['fits_16gb']} "
                      f"flops={res['full']['flops']:.3e} "
                      f"wire={res['full']['wire_bytes']/1e9:.3f}GB", flush=True)
                ok += 1
            except Exception as e:
                fail += 1
                print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
                with open(path + ".err", "w") as f:
                    f.write(traceback.format_exc())
    print(f"done: {ok} pass, {fail} fail, {skip} cached", flush=True)
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
