import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
import sys  # noqa: E402

if "--smoke-mesh" in sys.argv:  # tiny mesh for CI-scale tests
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config, get_shapes, list_archs  # noqa: E402
from repro.configs.base import GNNConfig, LMConfig, ShapeCell  # noqa: E402
from repro.launch import analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh, make_smoke_mesh  # noqa: E402
from repro.launch.sharding import rules_for, shard_input_specs, tree_shardings  # noqa: E402
from repro.models.layers import abstract_params, logical_axes, param_count  # noqa: E402
from repro.train import build_param_specs, build_serve_step, build_train_step  # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _n_scan_layers(cfg) -> int:
    if isinstance(cfg, LMConfig):
        return cfg.n_layers
    if isinstance(cfg, GNNConfig):
        return cfg.n_layers
    return getattr(cfg, "n_blocks", 1) or 1


def _abstract_state(cfg, cell, mesh):
    specs = build_param_specs(cfg, cell)
    axes = logical_axes(specs)
    rules = rules_for(cfg)
    shardings = tree_shardings(axes, specs, mesh, rules)
    dtype = cfg.dtype
    params_sds = abstract_params(specs, dtype, shardings)
    n_params = param_count(specs)
    return specs, params_sds, shardings, n_params


def _opt_state_sds(params_sds):
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding)
    return {
        "mu": jax.tree_util.tree_map(f32, params_sds),
        "nu": jax.tree_util.tree_map(f32, params_sds),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def apply_cfg_overrides(cfg, overrides: list[str]):
    """--cfg key=value (python literals) -> dataclasses.replace on the config."""
    import ast

    kw = {}
    for ov in overrides or ():
        key, _, val = ov.partition("=")
        try:
            kw[key] = ast.literal_eval(val)
        except (ValueError, SyntaxError):
            kw[key] = val
    return dataclasses.replace(cfg, **kw) if kw else cfg


def lower_cell(
    cfg,
    cell: ShapeCell,
    mesh,
    *,
    unroll: int = 1,
    remat: str = "none",
    grad_accum: int = 1,
):
    """Build + lower one (arch x shape) cell on a mesh. Returns lowered."""
    _, params_sds, _, _ = _abstract_state(cfg, cell, mesh)
    in_sds = shard_input_specs(cfg, cell, mesh)
    with jax.set_mesh(mesh):
        if cell.kind in ("train", "full_graph", "minibatch", "batched_graphs"):
            step = build_train_step(
                cfg, cell, remat=remat, unroll=unroll, grad_accum=grad_accum
            )
            state_sds = {"params": params_sds, "opt": _opt_state_sds(params_sds)}
            return jax.jit(step, donate_argnums=0).lower(state_sds, in_sds)
        step = build_serve_step(cfg, cell, unroll=unroll)
        if cell.kind == "decode":
            return jax.jit(step, donate_argnums=2).lower(
                params_sds, in_sds["tokens"], in_sds["cache"], in_sds["cache_len"]
            )
        return jax.jit(step).lower(params_sds, **in_sds)


def run_cell(
    arch: str,
    cell: ShapeCell,
    *,
    multi_pod: bool,
    smoke_mesh: bool = False,
    unroll: int = 1,
    remat: str = "none",
    grad_accum: int = 1,
    scan_corrected: bool = True,
    tag: str = "",
    cfg_overrides: list[str] | None = None,
) -> dict:
    cfg = apply_cfg_overrides(get_config(arch), cfg_overrides or [])
    mesh = (
        make_smoke_mesh(multi_pod=multi_pod)
        if smoke_mesh
        else make_production_mesh(multi_pod=multi_pod)
    )
    chips = mesh.devices.size
    mesh_name = ("multipod" if multi_pod else "pod") + ("-smoke" if smoke_mesh else "")
    rec: dict = {
        "arch": arch,
        "shape": cell.name,
        "kind": cell.kind,
        "mesh": mesh_name,
        "chips": int(chips),
        "remat": remat,
        "unroll": unroll,
        "grad_accum": grad_accum,
        "tag": tag,
        "cfg_overrides": list(cfg_overrides or []),
    }
    if (
        isinstance(cfg, LMConfig)
        and cell.name == "long_500k"
        and not cfg.sub_quadratic
    ):
        rec["status"] = "skipped"
        rec["reason"] = "pure full-attention arch; 500k dense KV excluded (DESIGN §4)"
        return rec

    t0 = time.time()
    try:
        lowered = lower_cell(
            cfg, cell, mesh, unroll=unroll, remat=remat, grad_accum=grad_accum
        )
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        return rec
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = analysis.parse_collectives(hlo)

    flops1 = float(ca.get("flops", 0.0))
    bytes1 = float(ca.get("bytes accessed", 0.0))
    coll1 = coll.total_bytes

    L = _n_scan_layers(cfg)
    corrected = False
    if scan_corrected and L >= 2 and L % 2 == 0 and unroll == 1:
        try:
            lowered2 = lower_cell(
                cfg, cell, mesh, unroll=2, remat=remat, grad_accum=grad_accum
            )
            compiled2 = lowered2.compile()
            ca2 = compiled2.cost_analysis() or {}
            coll2 = analysis.parse_collectives(compiled2.as_text())
            flops = analysis.scan_correct(flops1, float(ca2.get("flops", 0.0)), L)
            hbm = analysis.scan_correct(bytes1, float(ca2.get("bytes accessed", 0.0)), L)
            cbytes = analysis.scan_correct(coll1, coll2.total_bytes, L)
            corrected = True
        except Exception:  # noqa: BLE001 - fall back to uncorrected
            flops, hbm, cbytes = flops1, bytes1, coll1
    else:
        flops, hbm, cbytes = flops1, bytes1, coll1

    terms = analysis.RooflineTerms(
        flops=flops, hbm_bytes=hbm, coll_bytes=cbytes, chips=chips
    )
    mflops = analysis.model_flops(cfg, cell)
    mflops_chip = mflops / chips

    rec.update(
        status="ok",
        compile_s=round(t_compile, 2),
        scan_corrected=corrected,
        n_layers=L,
        memory={
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        },
        roofline=terms.as_dict(),
        collectives={
            "bytes_by_kind": coll.bytes_by_kind,
            "count_by_kind": coll.count_by_kind,
        },
        model_flops_global=mflops,
        model_flops_per_chip=mflops_chip,
        useful_flops_ratio=(mflops_chip / flops) if flops else None,
        roofline_fraction=terms.roofline_fraction(mflops_chip),
    )
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run: lower+compile cells")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape cell name (default: all)")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--smoke-mesh", action="store_true", help="8-device test mesh")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--unroll", type=int, default=1)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--no-scan-correct", action="store_true")
    ap.add_argument(
        "--cfg", action="append", default=[],
        help="config override key=value (python literal), repeatable",
    )
    ap.add_argument("--tag", default="", help="experiment tag for §Perf iterations")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    out_dir = Path(args.out) if args.out else RESULTS_DIR
    out_dir.mkdir(parents=True, exist_ok=True)

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for cell in get_shapes(arch):
            if args.shape and cell.name != args.shape:
                continue
            for mp in meshes:
                rec = run_cell(
                    arch,
                    cell,
                    multi_pod=mp,
                    smoke_mesh=args.smoke_mesh,
                    unroll=args.unroll,
                    remat=args.remat,
                    grad_accum=args.grad_accum,
                    scan_corrected=not args.no_scan_correct,
                    tag=args.tag,
                    cfg_overrides=args.cfg,
                )
                suffix = f"__{args.tag}" if args.tag else ""
                fname = f"{arch}__{cell.name}__{rec['mesh']}{suffix}.json"
                (out_dir / fname).write_text(json.dumps(rec, indent=1))
                status = rec["status"]
                n_ok += status == "ok"
                n_fail += status == "failed"
                n_skip += status == "skipped"
                if status == "ok":
                    r = rec["roofline"]
                    print(
                        f"[ok] {arch:18s} {cell.name:13s} {rec['mesh']:13s} "
                        f"compile={rec['compile_s']:7.1f}s peak_hbm="
                        f"{rec['memory']['peak_hbm_bytes']/2**30:7.2f}GiB "
                        f"dom={r['dominant']:10s} step={r['step_time_s']*1e3:9.3f}ms "
                        f"RF={rec['roofline_fraction']:.3f}",
                        flush=True,
                    )
                elif status == "skipped":
                    print(f"[skip] {arch:18s} {cell.name:13s} {rec['reason']}", flush=True)
                else:
                    print(
                        f"[FAIL] {arch:18s} {cell.name:13s} {rec['mesh']:13s} "
                        f"{rec['error']}",
                        flush=True,
                    )
    print(f"dry-run complete: ok={n_ok} failed={n_fail} skipped={n_skip}")
    if n_fail:
        sys.exit(1)


if __name__ == "__main__":
    main()
