import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod cross-pod traffic analysis: what should ride the slow links?

Compares per-device CROSS-POD bytes (pods joined by ~25 GB/s DCI vs
50 GB/s/link intra-pod ICI) for one train step on the 2x16x16 mesh:

  dp          data parallelism over pods (pjit baseline; bf16 grad AR)
  dp_bf16     explicit compressed sync (shard_map; bf16 all-gather wire)
  dp_int8     int8 wire + f32 scales (4x vs f32, 2x vs bf16)
  pp          pipeline parallelism over pods (GPipe; boundary activations)

Rule of thumb validated here: DP cross-pod ~ 2 x params-bytes; PP ~
n_micro x microbatch boundary activations -> PP wins when params >>
activations (qwen1.5-32b), DP wins for small models (tinyllama).

    PYTHONPATH=src python -m benchmarks.crosspod [--arch qwen1.5-32b]
"""
import argparse
import json

RESULTS = os.path.join(os.path.dirname(__file__), "perf_results")


def analyze(arch: str, n_micro: int = 8):
    import jax
    from repro.configs import make_run
    from repro.launch import hlo_analysis as ha
    from repro.launch.mesh import make_production_mesh
    from repro.models.model_zoo import Model
    from repro.optim.grad_compress import multipod_train_step
    from repro.parallel.pipeline import pipeline_train_step

    mesh = make_production_mesh(multi_pod=True)
    pod_size = 256
    out = {}

    def record(tag, compiled):
        span = ha.collective_bytes_by_span(compiled.as_text(), pod_size)
        out[tag] = span
        print(f"{arch:>16s} {tag:8s} cross-pod {span['cross']/1e9:8.2f} GB/dev"
              f"   intra {span['intra']/1e9:8.2f} GB/dev", flush=True)

    run = make_run(arch, "train_4k")
    with jax.set_mesh(mesh):
        model = Model(run)
        fn, args, in_sh, out_sh = model.dryrun_case(mesh)
        record("dp", jax.jit(fn, in_shardings=in_sh,
                             out_shardings=out_sh).lower(*args).compile())
        params, opt, batch = args
        for method in ("bf16", "int8"):
            step = multipod_train_step(model, mesh, method)
            record(f"dp_{method}",
                   jax.jit(step).lower(params, opt, batch).compile())
        if run.model.family in ("dense", "vlm", "moe") and \
                run.optimizer == "adamw":
            ok = all(reps % 2 == 0 for _, reps in run.model.stages())
            if ok:
                pstep = pipeline_train_step(model, mesh, n_micro=n_micro)
                record("pp", jax.jit(pstep).lower(params, opt,
                                                  batch).compile())
    os.makedirs(RESULTS, exist_ok=True)
    json.dump(out, open(os.path.join(
        RESULTS, f"crosspod_{arch}.json"), "w"), indent=1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--n-micro", type=int, default=8)
    args = ap.parse_args()
    archs = args.arch or ["tinyllama-1.1b", "qwen1.5-32b"]
    for a in archs:
        analyze(a, args.n_micro)


if __name__ == "__main__":
    main()
