"""Traffic kind ``chat``: a closed loop of ``slots`` chats served through
the program's own serving entry, ``launch.serve.Server``.

The model is the configuration file's registry ``arch`` with its
``overrides``; each key the file maps under ``registry`` must equal the
registry's value, so the program runs the sizes the file states. Its
weights are made from the seed by the plain reference
(``reference/<reference>.py``) on the device, in one jitted call, and
handed to the program under the paths the file's ``params`` names.

Every slot sends a request of ``prompt_len`` seeded random ids from the
vocabulary, decoded greedily for ``output_len`` tokens, and sends its next
one when it completes: ``Server.step_batch`` serves the ``slots`` requests
of a round together (prefill, then one decode submission per token), so a
round is one closed-loop cycle. Set-up serves one round of 3 tokens, which
compiles (or loads) every program a round runs.

The driver stands between the server and its two executors (``_Tap``):
it times each call, and keeps the logits of ``check_requests`` rows of
every round, of which a seeded reservoir keeps ``check_requests`` for the
check. Each token's host time is taken when the server hands it to the
request (``_Stamped``), which is where the client receives it. The host
time the driver spends on its own work inside the window is note
``driver_own_s``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import workmodel
from chipbench.harness import Call

WARM_TOKENS = 3          # a prefill, a decode after it, a decode after that


# one dispatch each: the driver's own device work inside the window
@jax.jit
def _rows_of(logits, rows):
    return jnp.take(logits, rows, axis=0)


@jax.jit
def _by_row(got):
    """The kept rows of each call of a round, (rows, calls, vocab)."""
    return jnp.stack(got, axis=1)


class _Stamped(list):
    """A request's tokens, with the host time each one arrived."""

    def __init__(self):
        super().__init__()
        self.times = []

    def append(self, token):
        self.times.append(time.perf_counter())
        super().append(token)


class _Tap:
    """One of the server's executors, seen by the driver: each call to the
    program's entry is timed, and the logits of the kept rows are kept."""

    def __init__(self, ex, label: str, drv: "Driver"):
        self.ex, self.label, self.drv = ex, label, drv

    def run(self, init, **kw):
        return self.submit(init, **kw).result()

    def submit(self, init, **kw):
        return _Handle(self, time.perf_counter(), self.ex.submit(init, **kw))

    def __getattr__(self, name):
        return getattr(self.ex, name)


class _Handle:
    def __init__(self, tap: _Tap, t0: float, handle):
        self.tap, self.t0, self.handle = tap, t0, handle

    def result(self, *a, **kw):
        out = self.handle.result(*a, **kw)
        tap = self.tap
        tap.drv._served(tap.label, self.t0, time.perf_counter(),
                        self.handle.trace_id, out["logits"])
        return out


class Driver:
    labels = ("prefill", "decode")

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.ref = importlib.import_module(
            f"chipbench.reference.{cfg['reference']}")
        self.dims = self.ref.Dims.of(cfg)
        self.slots = traffic["slots"]
        self.plen, self.out_len = traffic["prompt_len"], traffic["output_len"]
        self.n_keep = min(traffic["check_requests"], self.slots)
        self.pick = np.random.default_rng([seed, 2])
        self.calls, self.rounds, self.kept = [], [], []
        self.failed, self.seen, self.own_s = 0, 0, 0.0
        self.check_notes = {}
        self.tick = lambda: None
        self._ref = None

    # ------------------------------------------------------------ set-up
    def setup(self):
        from repro.configs import get_config
        from repro.configs.base import RunConfig, ShapeProfile
        from repro.launch.serve import Server
        from repro.models.model_zoo import Model
        cfg = self.cfg
        mc = dataclasses.replace(get_config(cfg["arch"]),
                                 **cfg.get("overrides", {}))
        wrong = {k: (getattr(mc, a), cfg[k])
                 for k, a in cfg["registry"].items() if getattr(mc, a) != cfg[k]}
        if wrong:
            raise ValueError(f"registry config differs from the file: {wrong}")
        # serving never differentiates, so no layer is rematerialised
        run = RunConfig(model=mc, shape=ShapeProfile(
            "serve", self.plen + self.out_len, self.slots, "decode"),
            remat="none")
        params = self._params(Model(run).abstract_params())
        self.srv = Server(run, params, policy=self.traffic["policy"],
                          max_batch=self.slots)
        self.srv.ex_prefill = _Tap(self.srv.ex_prefill, "prefill", self)
        self.srv.ex_decode = _Tap(self.srv.ex_decode, "decode", self)
        self._round(-1, WARM_TOKENS, record=False)

    def _params(self, abstract):
        """The reference's weights, nested as the program names them."""
        made = self.ref.weights(self.dims, self.seed)
        tree = {}
        for path, name in self.cfg["params"].items():
            node = tree
            *outer, leaf = path.split("/")
            for k in outer:
                node = node.setdefault(k, {})
            v = made
            for k in name.split("/"):
                v = v[k]
            node[leaf] = v
        want = jax.tree.map(lambda s: (s.shape, s.dtype), abstract)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
        if want != got:
            raise ValueError(f"weights do not match the program's "
                             f"parameters: {got} != {want}")
        return tree

    # ------------------------------------------------------------ window
    def _round(self, k: int, tokens: int, record: bool = True):
        """One closed-loop cycle: every slot's request, served together."""
        from repro.launch.serve import Request
        t0 = time.perf_counter()
        rng = np.random.default_rng([self.seed, 1, k + 1])
        prompts = rng.integers(0, self.dims.vocab_size,
                               (self.slots, self.plen), dtype=np.int32)
        rows = np.sort(rng.choice(self.slots, self.n_keep, replace=False))
        reqs = [Request(k * self.slots + i, prompts[i], max_new=tokens,
                        tokens=_Stamped()) for i in range(self.slots)]
        self._rows, self._record, self._got = jnp.asarray(rows), record, []
        self._own(t0)
        for r in reqs:
            self.srv.submit(r)
        try:
            self.srv.step_batch()
        except Exception:                   # counted; the round is lost
            self.failed += self.slots
            self.srv.queue.clear()
            self.check_notes.setdefault("first_error",
                                        traceback.format_exc(limit=3))
            return
        finally:
            got, self._got = self._got, []
        t0 = time.perf_counter()
        if not record:
            # the set-up round: compile what a round's capture runs
            got = got[:1] * self.out_len
        logits = _by_row(got)
        rows_logits = [logits[j] for j in range(len(rows))]
        if record:
            self.rounds.append(reqs)
            self._reservoir(reqs, rows, rows_logits)
        self._own(t0)

    def _own(self, t0):
        """Charge the host time since ``t0`` to the driver's own work in
        the window: drawing prompts, keeping logits, the reservoir."""
        if self._record:
            self.own_s += time.perf_counter() - t0

    def _served(self, label, t0, t1, run_id, logits):
        if self._record:
            self.calls.append(Call(label, t0, t1, run_id))
        self._got.append(_rows_of(logits, self._rows))
        self._own(t1)
        self.tick()

    def _reservoir(self, reqs, rows, rows_logits):
        """Algorithm R over every kept row of every round: a uniform
        sample, drawn from the seed, of ``n_keep`` finished requests."""
        for i, lg in zip(rows, rows_logits):
            slot = self.seen if self.seen < self.n_keep \
                else int(self.pick.integers(self.seen + 1))
            self.seen += 1
            if slot < self.n_keep:
                entry = (reqs[i], lg)
                if slot < len(self.kept):
                    self.kept[slot] = entry
                else:
                    self.kept.append(entry)

    def window(self, win, tick):
        self.tick = tick
        k = 0
        while win.open():
            self._round(k, self.out_len)
            k += 1
        self.tick = lambda: None

    def attempted(self, win) -> int:
        return len(self.rounds) * self.slots + self.failed

    def end_to_end(self, win) -> dict:
        """Tokens that reached their client in the window, over its
        seconds; and the gaps between consecutive tokens of every request
        completed in it."""
        t_end = win.t_end
        times = [r.tokens.times for rs in self.rounds for r in rs]
        delivered = sum(sum(1 for t in ts if t <= t_end) for ts in times)
        done = [ts for ts in times if ts and ts[-1] <= t_end]
        gaps = np.concatenate([np.diff(ts) for ts in done]) if done \
            else np.zeros(0)
        self.check_notes.update(
            requests_completed=len(done), itl_samples=int(gaps.size),
            driver_own_s=self.own_s,
            completed_tok_s=sum(map(len, done)) / win.seconds)
        out = {"serve_tok_s": delivered / win.seconds} if delivered else {}
        if gaps.size:
            out["itl_p90_ms"] = 1e3 * float(np.percentile(gaps, 90))
        return out

    def work(self) -> dict:
        """Model FLOPs of one call of each kind: a prefill runs every
        prompt position through the layers and the head at the last one;
        a decode runs one token of every slot through both."""
        flops = getattr(workmodel, f"{self.cfg['reference']}_token_flops")
        layers = flops(self.cfg, head=False)
        full = flops(self.cfg, head=True)
        return {"prefill_flops": self.slots * ((self.plen - 1) * layers
                                               + full),
                "decode_flops": self.slots * full}

    def spans(self):
        return self.srv.runtime.tracer.spans()

    def free(self):
        self.srv.close()
        self.srv = None
        gc.collect()

    # ------------------------------------------------------------- check
    def check(self, control=False) -> dict:
        """The sampled requests against the plain float32 reference's
        teacher-forced forward over prompt and served tokens: the logits at
        the last prompt position and at every decode step.

        ``logit_mae_rel``: the mean absolute gap between the served logits
        and the reference's, over the same gap of the reference computed at
        the configuration's precision (what a faithful program's rounding
        costs on this model and these tokens). ``tokens_unchosen``: served
        tokens that are not the greedy choice of their own logits, and
        ``tokens_missing``: tokens the sampled requests were owed and did not
        get, both compared exactly. Notes keep ``logit_mae`` itself and
        ``token_gap``, the widest gap by which a served token's reference
        logit lies below the reference's best.

        ``control=True`` puts the reference one precision below the
        configuration's in the program's place, judged the same way on the
        tokens it would choose."""
        lim = self.cfg["check_limits"]
        if not self.kept:
            return {}
        missing = sum(self.out_len - len(r.tokens) for r, _ in self.kept)
        kept = [(r, lg) for r, lg in self.kept
                if len(r.tokens) == self.out_len]
        checks = {"tokens_missing": (missing, 0)}
        if not kept:
            return checks
        if self._ref is None:
            toks = np.stack([np.concatenate([r.prompt, r.tokens[:-1]])
                             for r, _ in kept])
            pos = np.arange(self.plen - 1, toks.shape[1])
            ref = self.ref.logits(self.dims, self.seed, toks, pos)
            base = self.ref.logits(self.dims, self.seed, toks, pos,
                                   precision=self.dims.dtype)
            self._ref = (toks, pos, ref, float(jnp.mean(jnp.abs(base - ref))))
        toks, pos, ref, base_mae = self._ref
        if control:
            got = self.ref.logits(self.dims, self.seed, toks, pos,
                                  precision=self.ref.CONTROL[self.dims.dtype])
            picks = jnp.argmax(got, -1)
        else:
            got = jnp.stack([lg for _, lg in kept])
            picks = jnp.asarray(np.stack([r.tokens for r, _ in kept]))
        unchosen = int(jnp.sum(jnp.argmax(got, -1) != picks))
        mae = float(jnp.mean(jnp.abs(got - ref)))
        gap = float(jnp.max(jnp.max(ref, -1) - jnp.take_along_axis(
            ref, picks[..., None], -1)[..., 0]))
        self.check_notes.update(
            requests_compared=len(kept), positions_compared=int(picks.size),
            logit_mae=mae, logit_mae_config=base_mae, token_gap=gap)
        return {**checks, "tokens_unchosen": (unchosen, 0),
                "logit_mae_rel": (mae / base_mae, lim["logit_mae_rel"])}
