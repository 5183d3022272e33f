import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
against ShapeDtypeStruct stand-ins (no allocation), record memory analysis,
cost analysis and the collective schedule for the roofline report.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out benchmarks/artifacts]
"""

import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze, model_flops_for
from repro.models import model as model_mod
from repro.optim.optimizers import make_optimizer


#: The chip the dry-run's fake CPU devices stand in for (a v5e pod).
TARGET_KIND = "TPU v5 lite"


def _shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def build_lowering(arch: str, shape_name: str, mesh, *, optimizer="adamw"):
    """Returns (lowered, n_chips, model_flops)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = cfg.supports_shape(shape)
    if not ok:
        raise SkipShape(reason)

    batch_abs = {k: v for k, v in model_mod.input_specs(cfg, shape).items()}
    batch_specs = shd.batch_specs(cfg, shape, mesh)
    params_abs = model_mod.abstract_params(cfg)
    pspecs = shd.param_specs(cfg, params_abs, mesh)

    if shape.kind == "train":
        opt = make_optimizer(optimizer, 1e-4)
        opt_abs = model_mod.abstract_opt_state(opt, params_abs)
        ospecs = shd.opt_state_specs(cfg, opt_abs, params_abs, mesh)
        micro_sh = None
        grad_sh = None
        if cfg.grad_accum > 1:
            micro_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, P(None, *tuple(s))),
                batch_specs, is_leaf=lambda x: isinstance(x, P))
            grad_sh = _shardings(
                mesh, shd.zero_sharded_specs(cfg, params_abs, mesh))
        step_fn = model_mod.make_train_step(cfg, opt, grad_accum=cfg.grad_accum,
                                            microbatch_shardings=micro_sh,
                                            grad_shardings=grad_sh)
        in_sh = (_shardings(mesh, pspecs), _shardings(mesh, ospecs),
                 NamedSharding(mesh, P()), _shardings(mesh, batch_specs))
        out_sh = (_shardings(mesh, pspecs), _shardings(mesh, ospecs),
                  {"loss": NamedSharding(mesh, P())})
        step_abs = jax.ShapeDtypeStruct((), jax.numpy.int32)
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=(0, 1)).lower(
                params_abs, opt_abs, step_abs, batch_abs)
    elif shape.kind == "prefill":
        step_fn = model_mod.make_prefill_step(cfg)
        in_sh = (_shardings(mesh, pspecs), _shardings(mesh, batch_specs))
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn, in_shardings=in_sh).lower(
                params_abs, batch_abs)
    else:  # decode
        cache_abs = model_mod.abstract_cache(cfg, shape)
        cspecs = shd.cache_specs(cfg, shape, mesh, cache_abs)
        step_fn = model_mod.make_decode_step(cfg, shape)
        in_sh = (_shardings(mesh, pspecs), _shardings(mesh, cspecs),
                 _shardings(mesh, batch_specs))
        with jax.set_mesh(mesh):
            lowered = jax.jit(step_fn, in_shardings=in_sh,
                              donate_argnums=(1,)).lower(
                params_abs, cache_abs, batch_abs)

    n_chips = mesh.devices.size
    return lowered, n_chips, model_flops_for(cfg, shape)


class SkipShape(Exception):
    pass


def run_one(arch: str, shape_name: str, *, multi_pod: bool, out_dir: str = None,
            verbose: bool = True, tag_suffix: str = ""):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}{tag_suffix}"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        lowered, n_chips, mflops = build_lowering(arch, shape_name, mesh)
    except SkipShape as e:
        rec = {"tag": tag, "status": "SKIP", "reason": str(e)}
        _emit(rec, out_dir, tag, verbose)
        return rec

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    roof = analyze(cost, hlo, n_chips=n_chips, model_flops=mflops,
                   device_kind=TARGET_KIND)

    mem_rec = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    per_dev_total = (mem_rec.get("argument_size_in_bytes", 0)
                     + mem_rec.get("temp_size_in_bytes", 0))
    rec = {
        "tag": tag, "status": "OK", "arch": arch, "shape": shape_name,
        "mesh": mesh_name, "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_rec, "per_device_bytes": per_dev_total,
        "roofline": roof.to_dict(),
    }
    _emit(rec, out_dir, tag, verbose)
    return rec


def _emit(rec, out_dir, tag, verbose):
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        if rec["status"] == "OK":
            r = rec["roofline"]
            print(f"[OK]   {tag}: {rec['per_device_bytes']/2**30:.2f} GiB/dev, "
                  f"compute {r['compute_s']*1e3:.2f} ms, "
                  f"memory {r['memory_s']*1e3:.2f} ms, "
                  f"collective {r['collective_s']*1e3:.2f} ms "
                  f"-> {r['dominant']} bound "
                  f"(useful {r['useful_flops_ratio']*100:.0f}%, "
                  f"lower {rec['lower_s']}s compile {rec['compile_s']}s)",
                  flush=True)
        else:
            print(f"[SKIP] {tag}: {rec['reason']}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts")
    ap.add_argument("--flash-vjp", action="store_true",
                    help="enable the flash-attention custom-VJP perf path")
    ap.add_argument("--tag-suffix", default="",
                    help="suffix appended to artifact tags (perf iterations)")
    args = ap.parse_args()

    if args.flash_vjp:
        from repro.models import runtime
        runtime.set_flag("flash_vjp", True)

    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    run_one(arch, shape, multi_pod=mp, out_dir=args.out, tag_suffix=args.tag_suffix)
                except Exception:
                    failures.append((arch, shape, mp))
                    print(f"[FAIL] {arch} {shape} multi_pod={mp}", flush=True)
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    print("dry-run complete: all combinations lowered and compiled.")


if __name__ == "__main__":
    main()
