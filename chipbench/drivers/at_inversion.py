"""Traffic kind ``at_inversion``: adjoint-tomography inversions run back
to back, one ``EmeraldRuntime.submit`` per iteration.

Each inversion starts from the background model, raised by
``start_step`` m/s for every inversion before it, so that no two
submissions carry the same inputs (a runtime that reuses results of
identical inputs finds none to reuse, as in a deployment). It reads its
event's observations and runs ``iterations`` iterations in a namespace of
its own: every iteration's submission reads the model the previous one left
there and fetches ``chi``; the last also fetches the model. Events cycle.
Each event's target is the background plus Gaussian anomalies drawn from
the seed, drawn again until the receivers see them (the misfit of the
starting model reaches ``min_start_misfit``); its observations are made at
set-up by the plain reference solver, never by the program.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import Call
from chipbench.reference import at_wave

GEOMETRY = ("nx", "ny", "nz", "nt", "dx", "dt", "c0", "f0", "n_receivers",
            "lr")


class Driver:
    labels = ("at_iter",)

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.geo = {k: cfg[k] for k in GEOMETRY}
        self.rng = np.random.default_rng(seed)
        self.calls = []
        self.failed = 0
        self.inversions = []            # complete ones: event, chis, model
        self.check_notes = {}

    # ------------------------------------------------------------ set-up
    def _anomalies(self):
        t, g = self.traffic, self.geo
        dims = (g["nx"], g["ny"], g["nz"])
        out = []
        for _ in range(t["anomalies"]):
            centre = [self.rng.uniform(*f) * n
                      for f, n in zip(t["centre_frac"], dims)]
            r = self.rng.uniform(*t["radius_frac_of_nx"]) * g["nx"]
            amp = self.rng.uniform(*t["amplitude"]) * self.rng.choice((-1, 1))
            out.append((*centre, r, amp))
        return out

    def _event(self, start_seis):
        """Observations of a target the receivers see."""
        for _ in range(100):
            obs = at_wave.forward(jnp.asarray(at_wave.target_model(
                self.geo, self._anomalies())), self.geo)
            if float(at_wave.misfit(start_seis, obs)) \
                    >= self.traffic["min_start_misfit"]:
                return obs
        raise RuntimeError("no observable event in 100 draws")

    def setup(self):
        from repro.apps.adjoint_tomography import ATConfig, build_workflow
        from repro.core import (CostModel, EmeraldRuntime, MDSS,
                                MigrationManager, default_tiers)
        g = self.geo
        self.m0 = self.start(-1)
        self.obs = [self._event(at_wave.forward(self.m0, g))
                    for _ in range(self.traffic["events"])]
        tiers = default_tiers()
        cm = CostModel(tiers)
        mgr = MigrationManager(tiers, MDSS(tiers, cost_model=cm), cm)
        self.rt = EmeraldRuntime(mgr, name="chipbench-at")
        self.wf = build_workflow(ATConfig(**g))
        # warm: one iteration compiles (or loads) all four step programs
        h = self._submit("warm", {"model": self.m0, "obs": self.obs[0]},
                         last=True, record=False)
        h.release()

    def start(self, k: int):
        """The starting model of inversion ``k`` (-1: the warm-up's)."""
        g = self.geo
        return jnp.full((g["nx"], g["ny"], g["nz"]),
                        g["c0"] + self.traffic["start_step"] * (k + 1),
                        jnp.float32)

    def _submit(self, ns, init, *, last: bool, record: bool = True):
        fetch = ("chi", "model") if last else ("chi",)
        with jax.profiler.TraceAnnotation("chipbench:at_iter"):
            t0 = time.perf_counter()
            h = self.rt.submit(self.wf, init, policy=self.traffic["policy"],
                               namespace=ns, fetch=fetch)
            out = h.result(600)
            chi = float(out["chi"])
            t1 = time.perf_counter()
        if record:
            self.calls.append(Call("at_iter", t0, t1, h.trace_id))
        h.out, h.chi = out, chi
        return h

    # ------------------------------------------------------------ window
    def window(self, win, tick):
        iters = self.traffic["iterations_per_inversion"]
        k = 0
        while win.open():
            ev = k % len(self.obs)
            init = {"model": self.start(k), "obs": self.obs[ev]}
            chis, h = [], None
            for it in range(iters):
                if not win.open():
                    break
                try:
                    h = self._submit(f"inv{k}", init, last=it == iters - 1)
                except Exception:           # counted; the inversion is lost
                    self.failed += 1
                    break
                chis.append(h.chi)
                init = {}
                tick()
            if len(chis) == iters:
                self.inversions.append({"k": k, "event": ev, "chis": chis,
                                        "model": h.out["model"]})
            if h is not None:
                h.release()
            k += 1

    def attempted(self, win) -> int:
        return len(self.calls) + self.failed

    def end_to_end(self, win) -> dict:
        done = sum(1 for c in self.calls if c.t1 <= win.t_end)
        return {"at_iter_s": win.seconds / done} if done else {}

    def work(self) -> dict:
        from chipbench.workmodel import at_iteration_bytes
        g = self.geo
        return {"at_iter_bytes": at_iteration_bytes(
            g["nx"], g["ny"], g["nz"], g["nt"], g["n_receivers"])}

    def spans(self):
        return self.rt.tracer.spans()

    def free(self):
        self.rt.close()
        self.rt = self.wf = None

    # ------------------------------------------------------------- check
    def check(self, control: bool = False) -> dict:
        """Sampled complete inversions against the plain float32 reference:
        chi of every iteration, and the model's change over the inversion.

        ``control`` puts the plain reference, computed in bfloat16, in the
        program's place: the same inversions, judged the same way."""
        lim = self.cfg["check_limits"]
        pick = np.random.default_rng([self.seed, 1])
        n = min(self.traffic["check_inversions"], len(self.inversions))
        if n == 0:
            return {}
        chosen = pick.choice(len(self.inversions), n, replace=False)
        chi_err = model_err = 0.0
        for i in chosen:
            inv = self.inversions[int(i)]
            obs, m0 = self.obs[inv["event"]], self.start(inv["k"])
            chis, model = inv["chis"], inv["model"]
            if control:
                chis, model = at_wave.inversion(m0, obs, self.geo,
                                                len(chis), jnp.bfloat16)
            e = compare_inversion(chis, model, m0, obs, self.geo,
                                  jnp.float32)
            chi_err = max(chi_err, e["chi_rel_err"])
            model_err = max(model_err, e["model_rel_err"])
        self.check_notes = {"inversions_compared": n}
        return {"chi_rel_err": (chi_err, lim["chi_rel_err"]),
                "model_rel_err": (model_err, lim["model_rel_err"])}


def compare_inversion(chis, model, m0, obs, geo, dtype) -> dict:
    """The reference inversion from ``m0`` beside a served one."""
    ref_chis, ref_model = at_wave.inversion(m0, obs, geo, len(chis), dtype)
    chi_err = max(abs(a - b) / abs(b) for a, b in zip(chis, ref_chis))
    d_got = np.asarray(model, np.float64) - np.asarray(m0, np.float64)
    d_ref = np.asarray(ref_model, np.float64) - np.asarray(m0, np.float64)
    model_err = float(np.max(np.abs(d_got - d_ref))
                      / max(np.max(np.abs(d_ref)), 1e-30))
    return {"chi_rel_err": float(chi_err), "model_rel_err": model_err}
