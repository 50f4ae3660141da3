"""Pipeline parallelism over the ``pod`` axis (GPipe-style).

Cross-pod links (DCI) are the slowest in a multi-pod system. Data
parallelism over pods costs a full gradient reduction (~2 x params bytes)
per step; *pipeline* parallelism over pods costs only boundary activations
(n_micro x microbatch activation size) — far less for big models. This
module provides the PP alternative so the cross-pod axis can be chosen per
model (see EXPERIMENTS.md §Perf multi-pod analysis).

Mechanics (``shard_map`` manual over every mesh axis; the data/model axes
hold replicas inside the region, so activation rules are emptied there):

  * each LM stage's stacked layer params shard their leading (layers) dim
    over ``pod`` — pod *p* owns a contiguous slice of layers,
  * activations rotate pod->pod with ``ppermute`` on a GPipe schedule:
    at tick t, pod s processes microbatch t-s; pod 0 injects embeddings,
    the last pod computes loss on valid ticks,
  * reverse-mode AD transposes the ppermutes automatically, so one
    ``jax.grad`` yields the full pipelined backward,
  * embedding/head params are replicated across pods; their gradients are
    psum'd explicitly (manual region).

Constraints: every stage's layer count must divide by n_pods; global batch
must divide by n_micro.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.layers import lm_logits, rmsnorm, xent_loss
from repro.optim.optimizers import clip_by_global_norm


def _split_microbatches(batch, n_micro):
    def sp(x):
        return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
    return jax.tree.map(sp, batch)


def pipeline_train_step(model, mesh, n_micro: int) -> Callable:
    """Build a pipelined train step for a decoder-only dense/MoE LM."""
    assert "pod" in mesh.shape
    n_stages = mesh.shape["pod"]
    cfg, run = model.cfg, model.run
    # every mesh axis is manual in the region: no activation constraints
    rules = {k: v for k, v in model.rules.items() if not k.startswith("act_")}
    opt_update, schedule = model.opt_update, model.schedule
    stages = cfg.stages()
    assert not cfg.is_encoder_decoder, "PP path covers decoder-only archs"
    for _, reps in stages:
        assert reps % n_stages == 0, f"stage depth {reps} % pods {n_stages}"

    def per_pod(params, opt_state, batch):
        s_idx = jax.lax.axis_index("pod")

        def loss_fn(params):
            micro = _split_microbatches(batch, n_micro)
            B_m = micro["tokens"].shape[1]
            S = micro["tokens"].shape[2]
            T = n_micro + n_stages - 1
            buf = jnp.zeros((B_m, S, cfg.d_model), jnp.dtype(cfg.dtype))
            total = jnp.zeros((), jnp.float32)
            aux_total = jnp.zeros((), jnp.float32)
            for t in range(T):
                # stage 0 injects microbatch t (if any)
                if t < n_micro:
                    x_in = tfm.embed_inputs(
                        cfg, params, jax.tree.map(lambda v: v[t], micro),
                        rules)
                    buf = jnp.where(s_idx == 0, x_in, buf)
                # every pod applies its resident layer slice
                buf, _, aux = tfm.run_stages(cfg, run, params, buf, rules,
                                             mode="full")
                aux_total = aux_total + aux
                # last pod emits microbatch m = t - (n_stages-1)
                m = t - (n_stages - 1)
                if 0 <= m < n_micro:
                    h = rmsnorm(cfg, params["final_norm"], buf)
                    logits = lm_logits(cfg, params["embed"], h, rules)
                    loss_m = xent_loss(cfg, logits[:, :-1],
                                       micro["labels"][m][:, 1:])
                    total = total + jnp.where(s_idx == n_stages - 1,
                                              loss_m, 0.0)
                # rotate the pipe
                buf = jax.lax.ppermute(
                    buf, "pod",
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
            loss = jax.lax.psum(total, "pod") / n_micro
            return loss + jax.lax.psum(aux_total, "pod") / n_micro, loss

        (loss, xent), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        # layer grads are pod-resident; replicated params (embed, norms)
        # need the explicit cross-pod reduction
        def sync_replicated(path, g):
            name = path[0].key if path else ""
            if name.startswith("stage_"):
                return g
            # f32 cast: direct bf16 psum trips an XLA:CPU crash inside
            # shard_map (same bug as grad_compress.py)
            return jax.lax.psum(g.astype(jnp.float32), "pod").astype(g.dtype)
        grads = jax.tree_util.tree_map_with_path(sync_replicated, grads)
        grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
        lr = schedule(opt_state["step"] + 1)
        params, opt_state = opt_update(params, grads, opt_state, lr=lr)
        metrics = {"loss": loss, "xent": xent, "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    def param_specs(tree):
        """stage params: layers dim manual over pod; rest replicated."""
        def leaf_spec(path, leaf):
            name = path[0].key if path else ""
            return P("pod") if name.startswith("stage_") else P()
        return jax.tree_util.tree_map_with_path(leaf_spec, tree)

    assert run.optimizer == "adamw", "PP path wires adamw state sharding"
    p_specs = param_specs(model.abstract_params())
    o_specs = {"mu": p_specs, "nu": p_specs, "step": P()}

    return jax.shard_map(
        per_pod, mesh=mesh,
        in_specs=(p_specs, o_specs, P()),
        out_specs=(p_specs, o_specs, P()),
        check_vma=False)
