"""PartitionSpec rules per architecture family (DP / TP / EP / SP / FSDP).

Specs are derived from the parameter tree's *paths and shapes* (via
jax.eval_shape), so rules never drift from model code. A dimension is only
sharded when divisible by the mesh axis size — e.g. granite's 8 KV heads stay
replicated on a 16-wide model axis (Megatron-style GQA TP), while qwen2-moe's
60 experts fall back to expert-TP over d_ff (see DESIGN.md §5).

fsdp=True additionally shards the non-TP dimension of large matrices over the
data axis (ZeRO-3 style parameter sharding) — required for deepseek-v3-671b.
"""
from __future__ import annotations

import re
from typing import Callable

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def batch_axis(mesh) -> tuple[str, ...] | str:
    """The combined data-parallel axis ( ('pod','data') on multi-pod )."""
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data")) or "data"


def _div(shape, i, mesh, axis) -> bool:
    if axis is None or i >= len(shape):
        return False
    size = int(np.prod([mesh.shape[a] for a in
                        (axis if isinstance(axis, tuple) else (axis,))]))
    return shape[i] % size == 0 and shape[i] >= size


def _spec(shape, mesh, *axes):
    """PartitionSpec placing axes[i] on dim i when divisible, else None."""
    out = []
    for i in range(len(shape)):
        ax = axes[i] if i < len(axes) else None
        out.append(ax if _div(shape, i, mesh, ax) else None)
    return P(*out)


Rule = tuple[str, Callable]


def lm_rules(mesh, *, fsdp: bool = False) -> list[Rule]:
    """Path-regex -> spec rules for the transformer LM family.

    Layer-stacked params have a leading L dim (never sharded)."""
    dp = batch_axis(mesh) if fsdp else None
    mdl = "model"

    def stacked(fn):
        # apply fn to the trailing dims, leading stack dims unsharded
        def g(shape, mesh):
            core = fn(shape[-fn.ndim:], mesh)
            return P(*([None] * (len(shape) - fn.ndim) + list(core)))
        return g

    def mat(d_axis, f_axis, ndim=2):
        def fn(shape, mesh):
            return _spec(shape, mesh, d_axis, f_axis)
        fn.ndim = ndim
        return fn

    def expert_mat(in_dim: bool):
        def fn(shape, mesh):
            e, a, b = shape
            if _div(shape, 0, mesh, mdl):              # true EP (deepseek)
                return _spec(shape, mesh, mdl, dp, None)
            # expert-TP (qwen2-moe): ff dim over model + FSDP storage over
            # data. The model re-shards the weights at compute time
            # (transformer.MOE_WIN/WOUT_SHARDING): a data-sharded contraction
            # dim at the einsum collides with the token-slot data sharding
            # and XLA replicates the tokens instead (16x FLOP inflation).
            if in_dim:
                return _spec(shape, mesh, None, dp, mdl)    # (E, D, F)
            return _spec(shape, mesh, None, mdl, dp)        # (E, F, D)
        fn.ndim = 3
        return fn

    rules: list[Rule] = [
        (r"embed$", mat(mdl, dp)),
        (r"lm_head$", mat(dp, mdl)),
        (r"final_norm$|ln1$|ln2$|q_norm$|kv_norm$", mat(None, None, ndim=1)),
        (r"attn/(wq|wk|wv)$", stacked(mat(dp, mdl))),
        (r"attn/wo$", stacked(mat(mdl, dp))),
        (r"attn/wq_a$|attn/wkv_a$", stacked(mat(dp, None))),
        (r"attn/wq_b$|attn/wkv_b$", stacked(mat(None, mdl))),
        (r"router$", stacked(mat(dp, None))),
        (r"experts/(w_in|w_gate)$", stacked(expert_mat(True))),
        (r"experts/w_out$", stacked(expert_mat(False))),
        (r"(mlp|shared)/(w_in|w_gate)$", stacked(mat(dp, mdl))),
        (r"(mlp|shared)/w_out$", stacked(mat(mdl, dp))),
        (r"mtp/proj$", mat(dp, mdl)),
    ]
    return rules


def gnn_rules(mesh, **_kw) -> list[Rule]:
    """GNN params are small: replicate weights; data (edges) shards instead."""
    def rep(shape, mesh):
        return P(*([None] * len(shape)))
    return [(r".*", rep)]


def recsys_rules(mesh, **_kw) -> list[Rule]:
    """Embedding tables row-sharded over the model axis (the vocab is the big
    axis); small MLP/CIN weights replicated."""
    def table(shape, mesh):
        return _spec(shape, mesh, "model", None)

    def rep(shape, mesh):
        return P(*([None] * len(shape)))
    return [
        (r"embed$|lin_embed$", table),
        (r".*", rep),
    ]


def shard_map_compat(kernel, *, mesh, in_specs, out_specs,
                     check_rep: bool = True):
    """`jax.shard_map` with the replication check as a flag — the single
    place the repo spells it out; the KG engines and the transformer perf
    paths all route through here."""
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def kg_specs(axis: str = "shards") -> tuple[P, P, P, P, P]:
    """PartitionSpecs for the federated KG engine's operands, in the bucket
    engine's argument order: (triples, valid, perms, plan_data, params).

    The three KG-resident tensors carry the shard axis as their leading dim
    and live one-block-per-device on the mesh's shard axis; plan structure
    (PlanData) and request params are replicated — every device scans its own
    shard under the same plan. The same specs serve as shard_map in_specs and
    (via `kg_shardings`) as device placement for the server's resident copy.
    """
    return (P(axis), P(axis), P(axis), P(), P())


def kg_out_specs(axis: str = "shards") -> tuple[P, P, P]:
    """shard_map out_specs for (table, mask, overflow): per-shard results
    stacked on the shard axis."""
    return (P(axis), P(axis), P(axis))


def kg_shardings(mesh, axis: str = "shards"):
    """NamedShardings to device_put the shard-resident (triples, valid,
    perms) tensors onto a mesh, matching `kg_specs`' first three entries."""
    from jax.sharding import NamedSharding
    return tuple(NamedSharding(mesh, s) for s in kg_specs(axis)[:3])


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def make_param_specs(params_shape, mesh, rules: list[Rule]):
    """Map a params shape-tree (from jax.eval_shape) to a PartitionSpec tree."""
    def assign(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        for pat, fn in rules:
            if re.search(pat, ps):
                if hasattr(fn, "ndim"):
                    core = fn(shape[-fn.ndim:], mesh) if len(shape) >= fn.ndim \
                        else P(*([None] * len(shape)))
                    pad = len(shape) - len(core)
                    return P(*([None] * pad + list(core)))
                return fn(shape, mesh)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(assign, params_shape)
