import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this AOT-compiles the real step function (train / prefill /
decode) against ShapeDtypeStruct inputs carrying NamedShardings on the
production mesh — no arrays are allocated. It records:
  * memory_analysis()  — proves the cell fits per-device HBM,
  * cost_analysis()    — FLOPs / bytes for the roofline,
  * collective bytes   — parsed from the optimized (post-SPMD) HLO.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out EXP.json]
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.analysis import hlo_analysis, roofline
from repro.distributed import sharding
from repro.launch import mesh as mesh_lib, steps
from repro.models.transformer import SystemConfig
from repro.optim import optimizers


def _mesh_chips(mesh):
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             sys_overrides: Optional[dict] = None, mesh=None,
             keep_hlo: bool = False, verbose: bool = True,
             reduced: bool = False, shape=None) -> dict:
    """Lower + compile one cell; returns a result record (JSON-serializable).

    ``reduced=True`` uses the family-preserving smoke config and ``shape``
    overrides the registry entry — the benchmark drivers compile small cells
    on a 1x1 mesh this way instead of the 256-chip production grid."""
    cfg = configs.get_reduced(arch) if reduced else configs.get_config(arch)
    shape = shape if shape is not None else configs.SHAPES[shape_name]
    mesh_label = ("x".join(str(s) for s in mesh.devices.shape)
                  if mesh is not None
                  else "2x16x16" if multi_pod else "16x16")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_label}
    if not configs.shape_applicable(cfg, shape):
        rec.update(status="skipped",
                   reason="full-attention arch; long_500k needs sub-quadratic "
                          "serving (DESIGN.md §4)")
        return rec

    if mesh is None:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp, tp = sizes.get("data", 1), sizes.get("model", 1)
    pods = sizes.get("pod", 1)
    sys = steps.default_sys(cfg, shape, dp=dp, tp=tp, pods=pods)
    if sys_overrides:
        import dataclasses
        sys = dataclasses.replace(sys, **sys_overrides)
    rec["sys"] = {k: v for k, v in sys.__dict__.items()}

    t0 = time.time()
    try:
        with mesh:
            if shape.kind == "train":
                opt = optimizers.adamw(optimizers.warmup_cosine(3e-4, 100, 10000),
                                       weight_decay=0.1)
                step_fn = steps.make_train_step(cfg, sys, opt, mesh=mesh)
                state_sds = steps.state_specs_abstract(cfg, opt, mesh, sys)
                batch_sds = steps.input_specs(cfg, shape, mesh)
                jitted = jax.jit(step_fn, donate_argnums=(0,))
                lowered = jitted.lower(state_sds, batch_sds)
            elif shape.kind == "prefill":
                step_fn = steps.make_prefill_step(cfg, sys)
                param_sds = steps.param_specs_abstract(cfg, mesh, sys)
                batch_sds = steps.input_specs(cfg, shape, mesh)
                jitted = jax.jit(step_fn)
                lowered = jitted.lower(param_sds, batch_sds)
            else:  # decode
                step_fn = steps.make_decode_step(cfg, sys)
                param_sds = steps.param_specs_abstract(cfg, mesh, sys)
                cache_sds = steps.cache_specs_abstract(
                    cfg, shape, mesh, quant=sys.kv_quant)
                io = steps.input_specs(cfg, shape, mesh)
                jitted = jax.jit(step_fn, donate_argnums=(1,))
                lowered = jitted.lower(param_sds, cache_sds, io["tokens"],
                                       io["pos"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    except Exception as e:  # a failing cell is a bug; surface it loudly
        rec.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} FAILED: {e}")
        return rec

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    hcost = hlo_analysis.analyze(hlo)       # loop-aware per-device cost
    chips = _mesh_chips(mesh)
    aparams = jax.eval_shape(lambda: steps.model_init(jax.random.PRNGKey(0),
                                                      cfg))
    mflops = roofline.model_flops(cfg, shape, aparams)
    terms = roofline.terms_from_hlo(hcost, chips, mflops)

    rec.update(
        status="ok", lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory={k: int(getattr(mem, k, 0)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")},
        xla_cost={k: float(cost.get(k, 0.0)) for k in
                  ("flops", "bytes accessed", "transcendentals")},
        collectives={k: int(v) for k, v in hcost.coll.items()},
        collective_count=int(hcost.coll_count),
        roofline=terms.to_dict(),
    )
    per_dev_bytes = (rec["memory"]["argument_size_in_bytes"]
                     + rec["memory"]["temp_size_in_bytes"])
    rec["per_device_gb"] = round(per_dev_bytes / 2**30, 3)
    if keep_hlo:
        rec["hlo_collective_lines"] = [
            l.strip() for l in hlo.splitlines()
            if any(c in l for c in roofline._COLLECTIVES)][:200]
    if verbose:
        print(f"[dryrun] {arch:20s} {shape_name:12s} {rec['mesh']:8s} ok "
              f"compile={t_compile:6.1f}s perdev={rec['per_device_gb']:7.3f}GB "
              f"dom={terms.dominant:10s} "
              f"c/m/n={terms.compute_s:.2e}/{terms.memory_s:.2e}/"
              f"{terms.collective_s:.2e}s mfu={terms.mfu:.2f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = []
    if args.all:
        archs = configs.ARCH_IDS
        shapes = list(configs.SHAPES)
    else:
        archs = [args.arch] if args.arch else configs.ARCH_IDS
        shapes = [args.shape] if args.shape else list(configs.SHAPES)

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for mp in meshes:
        mesh = mesh_lib.make_production_mesh(multi_pod=mp)
        for arch in archs:
            for shape in shapes:
                results.append(run_cell(arch, shape, multi_pod=mp, mesh=mesh))

    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    fail = [r for r in results if r["status"] == "FAILED"]
    print(f"\n[dryrun] {ok} ok, {sk} skipped, {len(fail)} failed "
          f"of {len(results)} cells")
    for r in fail:
        print(f"  FAILED {r['arch']} x {r['shape']} x {r['mesh']}: {r['error']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {args.out}")
    return 0 if not fail else 1


if __name__ == "__main__":
    raise SystemExit(main())
