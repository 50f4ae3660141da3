"""Batched serving: prefill + decode loop as Emerald remotable steps.

A miniature continuous-batching server:

  * requests (token prompts) queue up; the scheduler packs up to
    ``max_batch`` into a slot-based batch,
  * ``prefill`` (remotable) builds the KV caches on the serving tier,
  * ``decode`` (remotable) advances every active slot one token per call;
    finished slots (EOS or length budget) free up,
  * params + caches stay resident on the serving tier via MDSS — decode
    offloads are code-only; only the sampled tokens cross the link,
  * both workflows execute over **one shared** :class:`EmeraldRuntime`
    (the server is a tenant of the long-lived scheduler, not the owner of
    per-call pools): decode submissions carry an *interactive* priority
    class, so on a fabric-backed tier they overtake batch tenants' queued
    tasks sharing the same runtime.

:class:`FrontDoor` is the many-tenant entry point on top: concurrent
single-request ``decode()`` calls from independent client threads
coalesce (``repro.core.batching.BatchCoalescer``) into ONE fused
interactive dispatch per flush window — per-task scheduling overhead is
paid once per batch, per-request deadlines can force an early flush, and
each participant is charged 1/k of the fused cost.

CLI demo (CPU-sized):
  python -m repro.launch.serve --arch tinyllama-1.1b --reduced
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig, RunConfig, ShapeProfile, reduced
from repro.core import (CostModel, EmeraldExecutor, EmeraldRuntime, MDSS,
                        MigrationManager, Workflow, default_tiers, partition)
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import Model

INTERACTIVE = 1          # broker dispatch class for latency-bound decodes


@dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,) int32
    max_new: int = 16
    tokens: List[int] = field(default_factory=list)
    done: bool = False


class Server:
    def __init__(self, run: RunConfig, params, *, policy: str = "annotate",
                 max_batch: Optional[int] = None,
                 runtime: Optional[EmeraldRuntime] = None):
        self.run = run
        self.model = Model(run)
        self.policy = policy
        self.max_batch = max_batch or run.shape.global_batch
        self._owns_runtime = runtime is None
        if runtime is None:
            self.tiers = default_tiers()
            self.cost_model = CostModel(self.tiers)
            self.mdss = MDSS(self.tiers, cost_model=self.cost_model)
            self.manager = MigrationManager(self.tiers, self.mdss,
                                            self.cost_model)
            runtime = EmeraldRuntime(self.manager, policy=policy,
                                     name="serve")
        else:                    # tenant of an existing multi-tenant runtime
            self.manager = runtime.manager
            self.tiers = self.manager.tiers
            self.cost_model = self.manager.cost_model
            self.mdss = runtime.mdss
        self.runtime = runtime
        self._build_workflows()
        self.params = params
        self.queue: List[Request] = []
        self.stats = {"prefills": 0, "decode_calls": 0, "tokens_out": 0}

    def close(self):
        # a tenant never tears down a shared runtime it doesn't own
        if self._owns_runtime:
            self.runtime.close()

    def _build_workflows(self):
        prefill, decode = self.model.prefill, self.model.decode_step

        def prefill_fn(params, batch, cache):
            logits, cache = prefill(params, batch, cache)
            return {"logits": logits, "cache": cache}

        def decode_fn(params, tokens, cache):
            logits, cache = decode(params, tokens, cache)
            return {"logits": logits, "cache": cache}

        wfp = Workflow("serve-prefill")
        for v in ("params", "batch", "cache"):
            wfp.var(v)
        wfp.step("prefill", prefill_fn, inputs=("params", "batch", "cache"),
                 outputs=("logits", "cache"), remotable=True)
        wfd = Workflow("serve-decode")
        for v in ("params", "tokens", "cache"):
            wfd.var(v)
        wfd.step("decode", decode_fn, inputs=("params", "tokens", "cache"),
                 outputs=("logits", "cache"), remotable=True)
        # two typed front-ends over the ONE shared runtime: prefill and
        # decode interleave with each other (and any co-tenant workflows)
        # on the same lanes, fabric, and MDSS
        self.ex_prefill = EmeraldExecutor(partition(wfp), self.manager,
                                          policy=self.policy,
                                          runtime=self.runtime)
        self.ex_decode = EmeraldExecutor(partition(wfd), self.manager,
                                         policy=self.policy,
                                         runtime=self.runtime)

    # ------------------------------------------------------------------ api
    def submit(self, req: Request):
        self.queue.append(req)

    def _pack(self, reqs: List[Request]):
        """Left-pad-free packing: common prefix length = min prompt len."""
        B = self.max_batch
        plen = min(len(r.prompt) for r in reqs)
        toks = np.zeros((B, plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i] = r.prompt[:plen]
        return jnp.asarray(toks), plen

    def step_batch(self) -> List[Request]:
        """Serve one packed batch from the queue to completion."""
        if not self.queue:
            return []
        reqs = self.queue[: self.max_batch]
        self.queue = self.queue[self.max_batch:]
        toks, plen = self._pack(reqs)
        out = self.ex_prefill.run(
            {"params": self.params, "batch": {"tokens": toks},
             "cache": self.model.init_cache()},
            fetch=("logits",))
        self.stats["prefills"] += 1
        last = jnp.argmax(out["logits"], -1)
        for i, r in enumerate(reqs):
            r.tokens.append(int(last[i]))
        max_new = max(r.max_new for r in reqs)
        budget = min(max_new - 1, self.run.shape.seq_len - plen - 1)
        for _ in range(budget):
            # latency-bound: decode tasks overtake batch tenants' queued
            # work when the runtime's cloud tier is fabric-backed
            out = self.ex_decode.submit({"tokens": last}, fetch=("logits",),
                                        priority=INTERACTIVE).result()
            self.stats["decode_calls"] += 1
            last = jnp.argmax(out["logits"], -1)
            for i, r in enumerate(reqs):
                if not r.done and len(r.tokens) < r.max_new:
                    r.tokens.append(int(last[i]))
                    self.stats["tokens_out"] += 1
                else:
                    r.done = True
            if all(r.done or len(r.tokens) >= r.max_new for r in reqs):
                break
        for r in reqs:
            r.done = True
        return reqs

    def transfer_report(self) -> Dict:
        offloads = [e for e in self.ex_decode.events if e.kind == "offload"]
        return {"decode_offloads": len(offloads),
                "decode_code_only": sum(1 for e in offloads
                                        if e.info.get("code_only")),
                "bytes_moved": dict(self.mdss.bytes_moved)}


class FrontDoor:
    """Coalescing decode entry point over one shared runtime.

    ``decode_fn(stacked_tokens)`` must be a *batched, row-independent*
    decode: it receives the (k, ...) stack of k concurrent requests'
    inputs and returns an array whose row i is request i's output —
    that row-independence is what makes cross-tenant fusion safe (see
    ``core/batching``). Each flush becomes ONE interactive-priority
    submission through the runtime, so k tenants' decodes pay one
    partition/validate/dispatch round trip instead of k.

    Client threads call ``decode(tokens, deadline_s=...)`` and block on
    the returned ticket; a request's deadline can flush the bucket
    early, and ``slo_ms`` arms the runtime's preemption guard for the
    fused runs themselves.
    """

    def __init__(self, runtime: EmeraldRuntime, decode_fn, *,
                 window_s: float = 0.004, max_batch: int = 32,
                 policy: str = "annotate", remotable: bool = False,
                 slo_ms: Optional[float] = None, name: str = "frontdoor"):
        from repro.core.batching import BatchCoalescer
        self.runtime = runtime
        self.slo_ms = slo_ms
        self._fp = getattr(decode_fn, "__name__", "decode")

        def fused_decode_fn(tokens):
            return {"logits": decode_fn(tokens)}

        wf = Workflow(f"{name}-fused-decode")
        wf.var("tokens")
        wf.step("decode", fused_decode_fn, inputs=("tokens",),
                outputs=("logits",), remotable=remotable, jax_step=False,
                slo_ms=slo_ms)
        self._ex = EmeraldExecutor(partition(wf), runtime.manager,
                                   policy=policy, runtime=runtime)
        self.coalescer = BatchCoalescer(
            self._fuse, window_s=window_s, max_batch=max_batch,
            metrics=runtime.metrics, tracer=runtime.tracer, name=name)
        runtime.attach_coalescer(self.coalescer)

    def _fuse(self, key, stacked: np.ndarray, k: int) -> np.ndarray:
        out = self._ex.submit({"tokens": stacked}, fetch=("logits",),
                              priority=INTERACTIVE).result()
        return np.asarray(out["logits"])

    # ------------------------------------------------------------------ api
    def decode(self, tokens, *, deadline_s: Optional[float] = None,
               charge=None):
        """Join the current batch for this (code, shape, dtype) bucket;
        returns a ticket — ``ticket.result()`` is this request's logits
        row. Requests with different shapes/dtypes never fuse."""
        arr = np.asarray(tokens)
        key = (self._fp, arr.shape, str(arr.dtype))
        return self.coalescer.submit(key, arr, deadline_s=deadline_s,
                                     charge=charge)

    def stats(self) -> dict:
        return self.coalescer.introspect()

    def close(self):
        self.coalescer.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized same-family config")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config(args.arch)) if args.reduced else get_config(args.arch)
    run = RunConfig(model=cfg, shape=ShapeProfile("serve", 128, 4, "decode"),
                    remat="none")
    model = Model(run)
    params = model.init_params(jax.random.PRNGKey(0))
    srv = Server(run, params)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        srv.submit(Request(rid, rng.integers(
            0, cfg.vocab_size, rng.integers(8, 32)).astype(np.int32),
            max_new=args.max_new))
    t0 = time.time()
    done: List[Request] = []
    while srv.queue:
        done += srv.step_batch()
    dt = time.time() - t0
    for r in done:
        print(f"req {r.rid}: {len(r.tokens)} tokens -> {r.tokens[:8]}...")
    print(f"{srv.stats} in {dt:.2f}s; transfers: {srv.transfer_report()}")
    srv.close()


if __name__ == "__main__":
    main()
