"""Logical-axis-rule sharding (MaxText-style), with divisibility fallback.

A *rule set* maps logical dim names (declared by ``ParamSpec.axes`` and by
activation constraints in the model code) to tuples of mesh axis names.
``resolve(rules, axes, shape, mesh)`` produces a ``PartitionSpec``:

  * mesh axes not present in the mesh are dropped,
  * a rule whose mesh-axis product does not divide the dim size is dropped
    (replicate instead) — this is what makes one rule set serve every arch
    (e.g. kv_heads=8 on a 16-way model axis falls back to replication while
    the KV *cache* stays sharded along its seq dim),
  * each mesh axis is used at most once per spec (first dim wins).

Presets:
  * ``dp_tp``  — paper-faithful baseline: batch over (pod,data); vocab/heads/
    ff/experts over model; params otherwise replicated.
  * ``fsdp``   — dp_tp + parameter/optimizer-state sharding over the data
    axis (ZeRO-3 style), the production default.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rule presets.  Logical names:
#   params : embed ff heads kv_heads head_dim vocab experts q_lora kv_lora
#            ssm_inner ssm_state dt_rank conv_k layers
#   acts   : act_batch act_seq act_embed act_ff act_heads act_kv_seq act_vocab
# ---------------------------------------------------------------------------

def _mk(d):
    return {k: tuple(v) if isinstance(v, (list, tuple)) else (v,)
            for k, v in d.items()}

DP_TP_RULES: Rules = _mk({
    # parameters
    "vocab": "model",
    "heads": "model",
    "ff": "model",
    "experts": "model",
    "ssm_inner": "model",
    "q_lora": "model",
    # activations
    "act_batch": ("pod", "data"),
    "act_ff": "model",
    "act_heads": "model",
    "act_vocab": "model",
    "act_ssm_inner": "model",
    "act_kv_seq": "model",     # decode KV cache sharded along sequence
    "act_experts": "model",
    "act_moe_group": ("pod", "data"),   # MoE token-group dim
})

FSDP_RULES: Rules = dict(DP_TP_RULES, **_mk({
    # additionally shard the big param matrices over the data axis (ZeRO-3).
    "embed": ("data",),
    "moe_ff": ("model",),
    "kv_lora": ("data",),
}))

# Pure ZeRO-3 data parallelism: the model axis becomes extra batch
# parallelism; params/optimizer state shard 256-way on their leading big
# dim; no tensor parallelism (no activation collectives). The right regime
# for models whose per-layer matmuls are too small to amortize TP
# collectives (see EXPERIMENTS.md §Perf, tinyllama hillclimb).
ZERO_DP_RULES: Rules = _mk({
    "embed": ("data", "model"),
    "ff": ("data", "model"),
    "vocab": ("data", "model"),
    "moe_ff": ("data", "model"),
    "experts": ("data", "model"),
    "ssm_inner": ("data", "model"),
    "q_lora": ("data", "model"),
    "kv_lora": ("data", "model"),
    "act_batch": ("pod", "data", "model"),
    "act_kv_seq": ("model",),
})

PRESETS: Dict[str, Rules] = {"dp_tp": DP_TP_RULES, "fsdp": FSDP_RULES,
                             "zero_dp": ZERO_DP_RULES}


def get_rules(preset: str, overrides: Sequence[Tuple[str, Tuple[str, ...]]] = ()) -> Rules:
    rules = dict(PRESETS[preset])
    for k, v in overrides:
        if v is None or v == ():
            rules.pop(k, None)
        else:
            rules[k] = tuple(v) if isinstance(v, (list, tuple)) else (v,)
    return rules


# ---------------------------------------------------------------------------
# Resolution.
# ---------------------------------------------------------------------------

def resolve(rules: Rules, axes: Tuple[Optional[str], ...],
            shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Logical axes + dim sizes -> PartitionSpec, with fallbacks."""
    used = set()
    parts = []
    for name, size in zip(axes, shape):
        entry: Tuple[str, ...] = rules.get(name, ()) if name else ()
        picked = []
        prod = 1
        for ax in entry:
            if ax not in mesh.shape or ax in used:
                continue
            nax = mesh.shape[ax]
            if size % (prod * nax) != 0:
                continue
            picked.append(ax)
            prod *= nax
        for ax in picked:
            used.add(ax)
        if not picked:
            parts.append(None)
        elif len(picked) == 1:
            parts.append(picked[0])
        else:
            parts.append(tuple(picked))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_pspecs(rules: Rules, axes_tree, abstract_tree, mesh: Mesh):
    """Pytree of logical-axes tuples + abstract values -> pytree of PartitionSpec."""
    def one(axes, aval):
        return resolve(rules, axes, aval.shape, mesh)
    return jax.tree.map(one, axes_tree, abstract_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def tree_shardings(rules: Rules, axes_tree, abstract_tree, mesh: Mesh):
    specs = tree_pspecs(rules, axes_tree, abstract_tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def constrain(x, rules: Rules, *names: Optional[str]):
    """Sharding-constrain an activation by logical dim names (no-op w/o mesh)."""
    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = resolve(rules, tuple(names), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def get_abstract_mesh():
    """The mesh set by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m
