import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first (before any jax-importing import): jax
locks the device count at first init, and only the dry-run may see 512
placeholder devices.

For each cell this produces:
  * proof the sharding config compiles (the deliverable's pass/fail),
  * ``memory_analysis`` (bytes/device — fits-or-not),
  * per-device HLO FLOPs / bytes / collective bytes with scan trip-count
    correction: one baseline compile + one compile per scanned stage with
    that stage unrolled by a known factor; costs are affine in the factor
    so the slope recovers exact per-layer costs (see hlo_analysis.py),
  * roofline terms + MODEL_FLOPS ratio (launch/roofline.py).

Results land in benchmarks/dryrun_results/*.json; EXPERIMENTS.md §Dry-run
and §Roofline are generated from them.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import all_cells, get_config, make_run
from repro.configs.base import SHAPES, shape_applicable
from repro.launch import hlo_analysis as ha
from repro.launch import roofline as rf
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import Model

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/dryrun_results")


def input_specs(arch: str, shape: str):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    run = make_run(arch, shape)
    model = Model(run)
    if run.shape.kind == "train":
        return {"params": model.abstract_params(),
                "opt_state": model.abstract_opt_state(),
                "batch": model.abstract_batch()}
    if run.shape.kind == "prefill":
        b = model.abstract_batch()
        b.pop("labels", None)
        return {"params": model.abstract_params(), "batch": b,
                "cache": model.abstract_cache()}
    return {"params": model.abstract_params(),
            "tokens": jax.ShapeDtypeStruct((run.shape.global_batch,), jax.numpy.int32),
            "cache": model.abstract_cache()}


def _unroll_divisor(reps: int, above: int = 1) -> int:
    """Smallest divisor of reps strictly greater than ``above``."""
    if reps <= above:
        return reps
    for u in range(above + 1, reps + 1):
        if reps % u == 0:
            return u
    return reps


def _compile_cell(run, mesh):
    model = Model(run)
    fn, args, in_sh, out_sh = model.dryrun_case(mesh)
    lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
    return model, lowered.compile()


def stage_plan(run) -> Dict[str, int]:
    """stage key -> scan reps (for trip-count correction)."""
    plan = {f"stage_{i}": reps
            for i, (_, reps) in enumerate(run.model.stages())}
    if run.model.is_encoder_decoder:
        plan["enc_stage"] = run.model.n_encoder_layers
    return plan


def run_cell(arch: str, shape: str, mesh_kind: str, *, slopes: bool = True,
             run_overrides: Optional[dict] = None) -> dict:
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind, "ok": False}
    t_start = time.time()
    try:
        run = make_run(arch, shape, **(run_overrides or {}))
    except ValueError as e:   # inapplicable cell (long_500k on full attention)
        rec.update(skipped=True, reason=str(e))
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        with jax.set_mesh(mesh):
            model, compiled = _compile_cell(run, mesh)
            base_cost = ha.cost_dict(compiled)
            base_coll = ha.collective_bytes(compiled.as_text())
            rec["memory"] = ha.memory_dict(compiled)
            rec["base_cost"] = base_cost
            rec["base_collectives"] = base_coll

            flops = base_cost["flops"]
            byts = base_cost["bytes"]
            coll = base_coll["total"]
            rec["stages"] = {}
            if slopes:
                u1 = run.scan_unroll      # F(u) is affine in the unroll u
                for key, reps in stage_plan(run).items():
                    if reps <= u1:
                        continue          # stage already fully unrolled
                    u = _unroll_divisor(reps, above=u1)
                    run_u = run.with_(unroll_stage=key, unroll_factor=u)
                    _, comp_u = _compile_cell(run_u, mesh)
                    cost_u = ha.cost_dict(comp_u)
                    coll_u = ha.collective_bytes(comp_u.as_text())["total"]
                    sl_f = (cost_u["flops"] - base_cost["flops"]) / (u - u1)
                    sl_b = (cost_u["bytes"] - base_cost["bytes"]) / (u - u1)
                    sl_c = (coll_u - base_coll["total"]) / (u - u1)
                    # SPMD may choose a cheaper collective strategy at the
                    # larger unroll (cross-layer CSE) — affinity holds for
                    # flops/bytes but can break for collectives; clamp.
                    clamped = sl_c < 0
                    sl_c = max(sl_c, 0.0)
                    flops += sl_f * (reps - u1)
                    byts += sl_b * (reps - u1)
                    coll += sl_c * (reps - u1)
                    rec["stages"][key] = {"reps": reps, "unroll": u,
                                          "base_unroll": u1,
                                          "slope_flops": sl_f,
                                          "slope_bytes": sl_b,
                                          "slope_coll": sl_c,
                                          "coll_slope_clamped": clamped}
            rec["per_device"] = {"flops": flops, "bytes": byts,
                                 "collective_bytes": coll}
            chips = 1
            for n in mesh.shape.values():
                chips *= n
            rec["chips"] = chips
            rec["roofline"] = rf.roofline_terms(flops, byts, coll)
            mf = rf.model_flops(run.model, run.shape)
            rec["model_flops"] = mf
            rec["hlo_flops_global"] = flops * chips
            rec["model_vs_hlo"] = mf / (flops * chips) if flops else 0.0
            rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=10)
    rec["wall_s"] = round(time.time() - t_start, 1)
    return rec


def save(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json".replace("/", "-")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-slopes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for arch, shape, ok, why in all_cells(include_inapplicable=True):
            cells.append((arch, shape))
    else:
        shapes = [args.shape] if args.shape else list(SHAPES)
        archs = [args.arch] if args.arch else []
        for a in archs:
            for s in shapes:
                cells.append((a, s))
    n_fail = 0
    for arch, shape in cells:
        for mk in meshes:
            fname = os.path.join(
                args.out, f"{arch}_{shape}_{mk}.json".replace("/", "-"))
            if args.skip_existing and os.path.exists(fname):
                try:
                    old = json.load(open(fname))
                    if old.get("ok") or old.get("skipped"):
                        print(f"[{arch:>20s} x {shape:<11s} x {mk:<6s}] cached",
                              flush=True)
                        continue
                except Exception:
                    pass
            # multi-pod pass proves sharding; slopes only needed single-pod
            slopes = (mk == "single") and not args.no_slopes
            rec = run_cell(arch, shape, mk, slopes=slopes)
            save(rec, args.out)
            if rec.get("skipped"):
                status = "SKIP (" + rec["reason"][:60] + ")"
            elif rec["ok"]:
                r = rec["roofline"]
                status = (f"ok {rec['wall_s']:6.1f}s  dominant={r['dominant']}"
                          f" bound={r['bound_s']*1e3:.1f}ms"
                          f" model/hlo={rec['model_vs_hlo']:.2f}")
            else:
                status = "FAIL " + rec["error"][:110]
                n_fail += 1
            print(f"[{arch:>20s} x {shape:<11s} x {mk:<6s}] {status}",
                  flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
