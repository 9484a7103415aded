import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (GSPMD partitions the step function
    over the production mesh without errors),
  * the program fits (``memory_analysis`` per-device bytes),
  * and it yields the roofline inputs (``cost_analysis`` FLOPs/bytes +
    collective bytes parsed from the compiled HLO).

Per single-pod cell we additionally compile unrolled depth-1 and depth-2
variants: XLA's HloCostAnalysis counts a scan body ONCE regardless of trip
count (verified empirically -- see EXPERIMENTS.md), so exact full-depth
costs come from the affine model  total(L) = base + L * (cost(L2) -
cost(L1)).  Results are written incrementally as JSON, one file per cell.

Usage:
  python -m repro.launch.dryrun                      # all 33 cells, both meshes
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
  python -m repro.launch.dryrun --mesh single --no-depth-variants
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, cells_for
from repro.distributed import sharding as SH
from repro.launch import steps as ST
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import build_model

OUT_DIR = pathlib.Path(os.environ.get("REPRO_DRYRUN_OUT",
                                      "results/dryrun"))


def _mesh_name(multi_pod: bool) -> str:
    return "pod2x16x16" if multi_pod else "pod16x16"


def _out_sharding_tree(mesh, struct_tree):
    """Replicated NamedShardings matching an output struct tree."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), struct_tree)


def lower_cell(cfg, shape, mesh, *, scan_layers=True, quant=None):
    """Lower + compile one cell. Returns (compiled, lowered).

    quant="ternary" (decode cells): abstract params pass through
    ``serving.quantize_for_serving`` -- the CUTIE 2-bit path; packed
    leaves get TP-on-last-dim specs.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    model = build_model(cfg)
    defs = model.defs()
    # Decode ALSO uses the 2-D (data x model) FSDP layout: with
    # execution_mode('serve') weights stay sharded at use, so per-device
    # weight reads are params/n_devices (Perf cycle 7). The 'serve'
    # replicated layout only pays off with very large decode batches.
    pspecs = SH.param_pspecs(defs, mesh, mode="train")
    params_abs = model.abstract_params()
    if quant == "ternary" and shape.kind == "decode":
        from repro.serving.serve import quantize_for_serving
        params_abs = jax.eval_shape(
            lambda p: quantize_for_serving(p)[0], params_abs)
        pspecs = _quantized_pspecs(pspecs, params_abs, mesh)
    param_sh = SH.shardings(mesh, pspecs)
    batch_abs = ST.input_specs(cfg, shape)

    if shape.kind == "train":
        opt_abs = ST.abstract_opt_state(cfg)
        opt_specs = SH.opt_pspecs(defs, mesh)
        opt_sh = SH.shardings(mesh, opt_specs)
        bspecs = SH.batch_pspecs(cfg, mesh, shape.global_batch, "train")
        batch_sh = {k: NamedSharding(mesh, bspecs.get(k, P()))
                    for k in batch_abs}
        step = ST.make_train_step(cfg, scan_layers=scan_layers)
        metrics_struct = jax.eval_shape(step, params_abs, opt_abs,
                                        batch_abs)[2]
        jitted = jax.jit(
            step,
            in_shardings=(param_sh, opt_sh, batch_sh),
            out_shardings=(param_sh, opt_sh,
                           _out_sharding_tree(mesh, metrics_struct)),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_abs, opt_abs, batch_abs)
    elif shape.kind == "prefill":
        bspecs = SH.batch_pspecs(cfg, mesh, shape.global_batch, "prefill")
        batch_sh = {k: NamedSharding(mesh, bspecs.get(k, P()))
                    for k in batch_abs}
        step = ST.make_prefill_step(cfg, scan_layers=scan_layers)
        b = SH._batch_dim_spec(mesh, shape.global_batch)
        vshard = ("model" if cfg.vocab_size %
                  dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
                  == 0 else None)
        out_sh = NamedSharding(mesh, P(b, vshard))
        jitted = jax.jit(step, in_shardings=(param_sh, batch_sh),
                         out_shardings=out_sh)
        lowered = jitted.lower(params_abs, batch_abs)
    else:  # decode
        cache_abs = ST.abstract_cache(cfg, shape)
        cspecs = SH.cache_pspecs(cfg, mesh, cache_abs, shape.global_batch)
        cache_sh = {k: NamedSharding(mesh, s) for k, s in cspecs.items()}
        b = SH._batch_dim_spec(mesh, shape.global_batch)
        tok_sh = NamedSharding(mesh, P(b, None))
        step = ST.make_serve_step(cfg, scan_layers=scan_layers)
        jitted = jax.jit(
            step,
            in_shardings=(param_sh, cache_sh, tok_sh),
            out_shardings=(tok_sh, cache_sh),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_abs, cache_abs,
                               jax.ShapeDtypeStruct(
                                   (shape.global_batch, 1), np.int32))
    compiled = lowered.compile()
    return compiled, lowered


def _quantized_pspecs(pspecs, params_abs, mesh):
    """Mirror float pspecs onto the quantized tree: packed keeps the
    source's output-dim sharding (divisibility-checked), scale follows."""
    from jax.sharding import PartitionSpec as P
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def walk(spec, abs_):
        if isinstance(abs_, dict) and "packed" in abs_:
            src = tuple(spec) + (None,) * (abs_["packed"].ndim - len(tuple(spec)))
            out_axis = src[-1]
            packed = [None] * abs_["packed"].ndim
            scale = [None] * abs_["scale"].ndim
            if (out_axis is not None
                    and abs_["packed"].shape[-1] % sizes.get(out_axis, 1) == 0):
                packed[-1] = out_axis
                scale[-1] = out_axis
            return {"packed": P(*packed), "scale": P(*scale)}
        if isinstance(abs_, dict):
            return {k: walk(spec[k], abs_[k]) for k in abs_}
        return spec

    return walk(pspecs, params_abs)


def analyze(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
    }
    if mem is not None:
        out["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
        }
    try:
        text = compiled.as_text()
        out["collectives"] = collective_bytes(text)
    except Exception as e:  # pragma: no cover
        out["collectives"] = {"error": str(e)}
    return out


def _depth_cfg(cfg, depth: int):
    """Reduced-depth config for the affine cost model (DESIGN.md Sec. 6).

    zamba2 uses depth = attn_every * k so each unit is one full stage
    (attn_every mamba layers + 1 shared-attn invocation); encdec scales
    encoder and decoder depth together.
    """
    kw = {"num_layers": depth}
    if cfg.family == "zamba2":
        kw = {"num_layers": cfg.attn_every * depth}
    if cfg.family == "encdec":
        kw.update(encoder_layers=depth, decoder_layers=depth)
    return dataclasses.replace(cfg, **kw)


def _depth_units(cfg) -> float:
    """Number of affine units in the full model."""
    if cfg.family == "zamba2":
        return cfg.num_layers / cfg.attn_every
    if cfg.family == "encdec":
        return float(cfg.encoder_layers)
    return float(cfg.num_layers)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             depth_variants: bool = True, force: bool = False,
             quant: str | None = None) -> dict:
    mesh_name = _mesh_name(multi_pod)
    suffix = f"__{quant}" if quant else ""
    out_path = OUT_DIR / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "num_devices": int(np.prod(mesh.devices.shape)),
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "status": "running",
    }
    rec["quant"] = quant
    t0 = time.time()
    try:
        with mesh:
            compiled, _ = lower_cell(cfg, shape, mesh, quant=quant)
        rec["full"] = analyze(compiled)
        rec["compile_s"] = round(time.time() - t0, 1)
        del compiled
        if depth_variants and not multi_pod:
            base_d = 1
            d1, d2 = base_d, 2 * base_d
            for tag, d in (("L1", d1), ("L2", d2)):
                cfg_d = _depth_cfg(cfg, d)
                t1 = time.time()
                with mesh:
                    comp_d, _ = lower_cell(cfg_d, shape, mesh,
                                           scan_layers=False, quant=quant)
                rec[tag] = analyze(comp_d)
                rec[tag]["compile_s"] = round(time.time() - t1, 1)
                del comp_d
            rec["depth_units"] = _depth_units(cfg)
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--no-depth-variants", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--quant", default=None, choices=["ternary", None])
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCHS
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    n_ok = n_err = 0
    for arch in archs:
        cfg = get_config(arch)
        cells = ([SHAPES[args.shape]] if args.shape
                 else cells_for(cfg))
        for cell in cells:
            if args.shape is None and cell not in cells_for(cfg):
                continue
            for mp in meshes:
                t0 = time.time()
                rec = run_cell(arch, cell.name, mp,
                               depth_variants=not args.no_depth_variants,
                               force=args.force, quant=args.quant)
                ok = rec["status"] == "ok"
                n_ok += ok
                n_err += not ok
                print(f"[{time.strftime('%H:%M:%S')}] {arch} x {cell.name}"
                      f" x {_mesh_name(mp)}: {rec['status']}"
                      f" ({rec.get('total_s', 0)}s)"
                      + ("" if ok else f"  {rec.get('error', '')[:200]}"),
                      flush=True)
    print(f"dry-run done: {n_ok} ok, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
