"""Smoke test of Emerald's main path on one TPU chip.

    python chip_smoke.py

Runs in one process on the first TPU device, phases in order, each checked
against a plain reference on the same chip:

  A. the paper's adjoint-tomography workflow at the Fig. 12 grid
     (208x44x46, nt 200): two iterations through ``EmeraldRuntime.submit``
     with ``policy="annotate"`` (steps 2-4 remotable), compared with the
     four step functions called in order as plain jitted functions;
  B. tinyllama-1.1b at its published widths in bf16 (random weights from a
     seed) served through ``launch.serve.Server``: 4 requests of 256 tokens,
     8 new tokens each. Every decode step's logits (jnp attention over the
     KV cache) are compared with a full-sequence forward over the prompt
     plus the generated tokens (the prefill path, Pallas flash attention);
  C. one ``selective_scan`` call at falcon-mamba-7b widths (B 1, L 2048,
     d_inner 8192, N 16) in float32 against ``selective_scan_ref``.

B and C also check that their compiled programs contain the Pallas kernel
(``tpu_custom_call``). Earlier lines carry diagnostics per phase: wall and
compile seconds, persistent-cache hits, peak device bytes and the
comparison errors. None of them is a performance measurement. The last
line is one JSON object naming the device. Any failure exits non-zero
before that line is printed; without a TPU the script fails at once.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402
from jax import monitoring                                   # noqa: E402

from repro.apps.adjoint_tomography import (                  # noqa: E402
    FIG12, build_workflow, make_observations, starting_model, step_forward,
    step_kernel, step_misfit, step_update)
from repro.configs import get_config                         # noqa: E402
from repro.configs.base import RunConfig, ShapeProfile       # noqa: E402
from repro.core import (CostModel, EmeraldRuntime, MDSS,     # noqa: E402
                        MigrationManager, default_tiers)
from repro.kernels.mamba_scan.ops import selective_scan      # noqa: E402
from repro.kernels.mamba_scan.ref import selective_scan_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import Request, Server               # noqa: E402
from repro.models import transformer as tfm                  # noqa: E402
from repro.models.layers import lm_logits, rmsnorm           # noqa: E402
from repro.models.model_zoo import Model                     # noqa: E402

SEED = 0
KERNEL = "tpu_custom_call"
AT_RTOL = 1e-5         # float32 round-off: same step fns, same chip
LOGIT_TOL = 5e-2       # bf16 model: max |decode - full| / max |full|
SCAN_TOL = 1e-4        # float32 sequential vs associative scan

_compile = {"s": 0.0, "n": 0, "cache_hits": 0}


def _on_duration(name, secs, **_):
    if name == "/jax/core/compile/backend_compile_duration":
        _compile["s"] += secs
        _compile["n"] += 1


def _on_event(name, **_):
    if name == "/jax/compilation_cache/cache_hits":
        _compile["cache_hits"] += 1


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _require(ok: bool, msg: str):
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def _check(name: str, value: float, tol: float):
    print(f"  {name}: {value:.3e} (tolerance {tol:.0e})", flush=True)
    _require(bool(np.isfinite(value)) and value <= tol,
             f"{name} {value} > {tol}")


# ---------------------------------------------------------------------------
# Phase A: the paper's adjoint-tomography workflow.
# ---------------------------------------------------------------------------

def phase_at(cfg=FIG12, iters: int = 2) -> dict:
    obs = make_observations(cfg)
    m0 = starting_model(cfg)
    tiers = default_tiers()
    cm = CostModel(tiers)
    mdss = MDSS(tiers, cost_model=cm)
    mgr = MigrationManager(tiers, mdss, cm)
    wf = build_workflow(cfg)
    got, offloads = [], 0
    with EmeraldRuntime(mgr, name="chip-smoke-at") as rt:
        init = {"model": m0, "obs": obs}
        for _ in range(iters):
            # later iterations read the updated model from the namespace
            h = rt.submit(wf, init, policy="annotate",
                          namespace="at", fetch=("chi", "model"))
            out = h.result(600)
            offloads += sum(1 for e in h.events if e.kind == "offload")
            got.append((float(out["chi"]), np.asarray(out["model"])))
            init = {}
    _require(offloads == 3 * iters, f"{offloads} offloads, want steps 2-4")

    fwd, mis, ker, upd = (jax.jit(f(cfg)) for f in (
        step_forward, step_misfit, step_kernel, step_update))
    m = m0
    errs = {}
    for it, (chi, model) in enumerate(got):
        syn = fwd(model=m)["syn"]
        chi_ref = float(mis(syn=syn, obs=obs)["chi"])
        m = upd(model=m, grad=ker(model=m, obs=obs)["grad"])["model"]
        errs[f"chi[{it}]"] = abs(chi - chi_ref) / abs(chi_ref)
        errs[f"model[{it}]"] = _rel_err(model, m)
    for k, v in errs.items():
        _check(f"rel err {k}", v, AT_RTOL)
    return {"grid": cfg.mesh_name, "nt": cfg.nt, "iters": iters,
            "offloads": offloads, "chi": [c for c, _ in got], **errs}


# ---------------------------------------------------------------------------
# Phase B: LM serving at full published width.
# ---------------------------------------------------------------------------

class _DecodeRecorder:
    """Keeps the logits of every decode submission the server makes."""

    def __init__(self, ex):
        self.ex, self.logits = ex, []

    def submit(self, *a, **kw):
        handle = self.ex.submit(*a, **kw)
        self.logits.append(np.asarray(handle.result()["logits"]))
        return handle

    def __getattr__(self, name):
        return getattr(self.ex, name)


def phase_serve(cfg=None, *, n_req: int = 4, prompt_len: int = 256,
                max_new: int = 8, seq_len: int = 1024) -> dict:
    cfg = cfg or get_config("tinyllama-1.1b")
    run = RunConfig(model=cfg, shape=ShapeProfile("serve", seq_len, n_req,
                                                  "decode"), remat="none")
    model = Model(run)
    params = model.init_params(jax.random.PRNGKey(SEED))
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (n_req, prompt_len)).astype(np.int32)

    srv = Server(run, params, policy="annotate")
    rec = srv.ex_decode = _DecodeRecorder(srv.ex_decode)
    for rid in range(n_req):
        srv.submit(Request(rid, prompts[rid], max_new=max_new))
    done = srv.step_batch()
    srv.close()
    lens = [len(r.tokens) for r in done]
    _require(lens == [max_new] * n_req, f"tokens per request {lens}")
    _require(len(rec.logits) == max_new - 1,
             f"{len(rec.logits)} decode steps")

    # the server's own prefill program: same fn, same shapes
    prefill = srv.ex_prefill.pwf.workflow.steps["prefill"].fn
    prefill_hlo = jax.jit(prefill).lower(
        params=params, batch={"tokens": jnp.asarray(prompts)},
        cache=model.init_cache()).compile().as_text()

    rules = model.rules

    def full_logits(params, tokens):
        x = tfm.embed_inputs(cfg, params, {"tokens": tokens}, rules)
        x, _, _ = tfm.run_stages(cfg, run, params, x, rules, mode="full")
        return lm_logits(cfg, params["embed"],
                         rmsnorm(cfg, params["final_norm"], x), rules)

    seqs = jnp.asarray(np.concatenate(
        [prompts, np.array([r.tokens for r in done], np.int32)], axis=1))
    full = jax.jit(full_logits).lower(params, seqs).compile()
    ref = np.asarray(full(params, seqs))
    _require(KERNEL in prefill_hlo, "prefill program has no Pallas kernel")
    _require(KERNEL in full.as_text(), "full forward has no Pallas kernel")

    errs = [_rel_err(lg, ref[:, prompt_len + i])
            for i, lg in enumerate(rec.logits)]
    agree = sum(int(np.sum(np.argmax(lg, -1) == np.argmax(
        ref[:, prompt_len + i], -1))) for i, lg in enumerate(rec.logits))
    for i, e in enumerate(errs):
        _check(f"decode step {i} logits vs full forward", e, LOGIT_TOL)
    return {"arch": cfg.name, "requests": n_req, "prompt_len": prompt_len,
            "new_tokens": max_new, "decode_steps": len(rec.logits),
            "max_logit_rel_err": max(errs),
            "greedy_agree": f"{agree}/{len(errs) * n_req}",
            "stats": srv.stats}


# ---------------------------------------------------------------------------
# Phase C: the Mamba selective-scan kernel.
# ---------------------------------------------------------------------------

def phase_scan(Bt: int = 1, L: int = 2048, di: int = 8192,
               N: int = 16) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(SEED), 7)
    f32 = jnp.float32
    args = (jax.random.normal(ks[0], (Bt, L, di), f32),
            jax.random.uniform(ks[1], (Bt, L, di), f32, 1e-3, 0.1),
            -jax.random.uniform(ks[2], (di, N), f32, 0.5, 2.0),
            jax.random.normal(ks[3], (Bt, L, N), f32),
            jax.random.normal(ks[4], (Bt, L, N), f32),
            jax.random.normal(ks[5], (di,), f32),
            jax.random.normal(ks[6], (Bt, di, N), f32))
    scan = jax.jit(selective_scan).lower(*args).compile()
    _require(KERNEL in scan.as_text(), "selective_scan has no Pallas kernel")
    y, h = scan(*args)
    y_ref, h_ref = jax.jit(selective_scan_ref)(*args)
    err_y, err_h = _rel_err(y, y_ref), _rel_err(h, h_ref)
    _check("rel err y", err_y, SCAN_TOL)
    _check("rel err h_last", err_h, SCAN_TOL)
    return {"shape": [Bt, L, di, N], "err_y": err_y, "err_h": err_h}


# ---------------------------------------------------------------------------

def _run_phase(name: str, fn, dev) -> dict:
    print(f"phase {name}", flush=True)
    c0 = dict(_compile)
    t0 = time.perf_counter()
    info = fn()
    info["wall_s"] = time.perf_counter() - t0
    info["compile_s"] = _compile["s"] - c0["s"]
    info["compiles"] = _compile["n"] - c0["n"]
    info["cache_hits"] = _compile["cache_hits"] - c0["cache_hits"]
    stats = dev.memory_stats() or {}
    info["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    print(f"phase {name} passed: {json.dumps(info, default=str)}",
          flush=True)
    gc.collect()
    return info


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _run_phase("A (adjoint tomography)", phase_at, dev)
    _run_phase("B (tinyllama-1.1b serving)", phase_serve, dev)
    _run_phase("C (mamba selective scan)", phase_scan, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
