"""Trip-count-exact cost accounting for scanned LM programs.

XLA's HloCostAnalysis counts a while-loop body ONCE (verified in
EXPERIMENTS.md §Dry-run methodology), so a scan-over-layers train step
under-reports FLOPs by ~L x accum. This module lowers the scan-free
components — one transformer layer (fwd+bwd), the embedding/head/loss, the
optimizer — under the same mesh/shardings, where counting is exact, and
recombines:

  train:   accum * (L_dense*layer_d + L_moe*layer_m + head) + opt
  prefill: L_dense*layer_d + L_moe*layer_m + head_last
  decode:  L_dense*layer_d + L_moe*layer_m + head_last

Collective bytes recombine the same way (a per-layer FSDP all-gather really
runs L x accum times). Peak memory always comes from the real full program.
"""
from __future__ import annotations

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.sharding.rules import batch_axis, lm_rules, make_param_specs



def _cost_of(fn, args, in_sh, mesh, out_sh=None):
    with mesh:
        kw = {} if out_sh is None else {"out_shardings": out_sh}
        compiled = jax.jit(fn, in_shardings=in_sh,
                           **kw).lower(*args).compile()
    from repro.engine.batch import cost_dict
    from repro.launch.dryrun import collective_bytes
    cost = cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collectives": coll["per_kind_bytes"],
            "collective_total": coll["total_bytes"]}


def _scale(c: dict, k: float) -> dict:
    return {"flops": c["flops"] * k, "bytes": c["bytes"] * k,
            "collectives": {kk: v * k for kk, v in c["collectives"].items()},
            "collective_total": c["collective_total"] * k}


def _add(*cs) -> dict:
    out = {"flops": 0.0, "bytes": 0.0, "collective_total": 0.0,
           "collectives": {}}
    for c in cs:
        out["flops"] += c["flops"]
        out["bytes"] += c["bytes"]
        out["collective_total"] += c["collective_total"]
        for k, v in c["collectives"].items():
            out["collectives"][k] = out["collectives"].get(k, 0.0) + v
    return out


def _layer_tree_slice(stacked_shape, stacked_specs):
    """Shapes/specs for ONE layer (drop the leading stack dim)."""
    one = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), stacked_shape)
    specs = jax.tree.map(lambda s: P(*s[1:]), stacked_specs)
    return one, specs


def lm_component_costs(arch_id: str, shape_id: str, mesh) -> dict:
    from repro.launch.cells import LM_SHAPE_DEFS, LM_SERVE_FSDP, LM_TRAIN_KNOBS
    from repro.configs import get_arch
    from repro.models import transformer as tr

    cfg = get_arch(arch_id).full()
    sd = LM_SHAPE_DEFS[shape_id]
    dp = batch_axis(mesh)
    ns = lambda s: NamedSharding(mesh, s)
    batch_div = sd["batch"] % int(np.prod(
        [mesh.shape[a] for a in (dp if isinstance(dp, tuple) else (dp,))])) == 0
    tr.ACT_SHARDING = ns(P(dp if batch_div and sd["batch"] > 1 else None,
                           None, None))
    if cfg.moe:
        e_ax = "model" if cfg.n_experts % int(mesh.shape["model"]) == 0 else None
        cap_ax = dp if batch_div and sd["batch"] > 1 else None
        tr.MOE_SHARDING = ns(P(e_ax, cap_ax, None))
        if e_ax is None:  # expert-TP compute layout (gathers the FSDP dim)
            tr.MOE_WIN_SHARDING = ns(P(None, None, "model"))
            tr.MOE_WOUT_SHARDING = ns(P(None, "model", None))
        else:             # EP compute layout
            tr.MOE_WIN_SHARDING = ns(P("model", None, None))
            tr.MOE_WOUT_SHARDING = ns(P("model", None, None))
        from repro.launch import cells as _c2
        if _c2.MOE_IMPL == "shard_map":  # §Perf iteration A (EP + expert-TP)
            tr.MOE_SHARD_MAP = {"mesh": mesh, "dp": dp, "model": "model"}
        else:
            tr.MOE_SHARD_MAP = None
    else:
        tr.MOE_SHARDING = None
        tr.MOE_WIN_SHARDING = None
        tr.MOE_WOUT_SHARDING = None
        tr.MOE_SHARD_MAP = None
    train = shape_id == "train_4k"
    if shape_id in ("decode_32k", "long_500k"):
        from repro.launch import cells as _cells
        tr.CACHE_UPDATE = _cells.CACHE_UPDATE_MODE
        tr.DECODE_SHARD_MAP = ({"mesh": mesh, "dp": dp, "model": "model"}
                               if _cells.CACHE_UPDATE_MODE == "masked"
                               else None)
    else:
        tr.DECODE_SHARD_MAP = None
    fsdp = (LM_TRAIN_KNOBS[arch_id]["fsdp"] if train
            else LM_SERVE_FSDP.get(arch_id, False))
    pshape = jax.eval_shape(partial(tr.init_params, cfg),
                            jax.random.PRNGKey(0))
    pspecs = make_param_specs(pshape, mesh, lm_rules(mesh, fsdp=fsdp))

    n_dense = cfg.first_dense_layers if cfg.moe else cfg.n_layers
    n_moe = cfg.n_layers - n_dense if cfg.moe else 0

    if train:
        accum = LM_TRAIN_KNOBS[arch_id]["accum"]
        dp_sz = int(np.prod([mesh.shape[a] for a in
                             (dp if isinstance(dp, tuple) else (dp,))]))
        while accum > 1 and (sd["batch"] // accum) % dp_sz != 0:
            accum //= 2
        B = sd["batch"] // accum
        S = sd["seq"]
    elif shape_id == "prefill_32k":
        accum, B, S = 1, sd["batch"], sd["seq"]
    else:
        accum, B, S = 1, sd["batch"], 1

    x_sh = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.dtype(cfg.dtype))
    x_spec = P(dp, None, None) if B > 1 else P(None, None, None)
    positions = jnp.arange(1)  # placeholder; rebuilt inside fns

    comps = {}

    def layer_cost(stack_key: str, moe: bool):
        one, ospec = _layer_tree_slice(pshape[stack_key], pspecs[stack_key])
        if shape_id in ("decode_32k", "long_500k"):
            T = sd["seq"]
            cshape = jax.eval_shape(partial(tr.init_cache, cfg, B, T))
            sub = "moe" if moe else "dense"
            cache_one = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                cshape[sub])
            from repro.launch.cells import _cache_specs_tree
            seq_axes = ("data", "model") if B == 1 else "model"
            cspec_full = _cache_specs_tree(cfg, cshape, mesh, seq_axes)
            cache_spec = jax.tree.map(lambda s: P(*s[1:]), cspec_full[sub])

            def fn(lp, x, ca, cb):
                pos = jnp.full((B, 1), T - 1, jnp.int32)
                out, _ = tr._layer_fwd(lp, cfg, x, pos, T - 1, moe,
                                       cache=(ca, cb, jnp.int32(T - 1)))
                return out
            return _cost_of(fn, (one, x_sh, *cache_one),
                            (jax.tree.map(ns, ospec), ns(x_spec),
                             *jax.tree.map(ns, cache_spec)), mesh)

        cfg_l = replace(cfg, attn_chunk=0)

        def fwd(lp, x):
            pos = jnp.arange(S)[None, :]
            out, _ = tr._layer_fwd(lp, cfg_l, x, pos, 0, moe)
            return out

        if train:
            def fn(lp, x):
                f = lambda lp_, x_: jnp.sum(
                    jax.checkpoint(fwd)(lp_, x_).astype(jnp.float32))
                return jax.grad(f, argnums=(0, 1))(lp, x)
            # grads land in the params' sharding (reduce-scatter, ZeRO-2),
            # matching the real train step's accumulator constraint
            return _cost_of(fn, (one, x_sh),
                            (jax.tree.map(ns, ospec), ns(x_spec)), mesh,
                            out_sh=(jax.tree.map(ns, ospec), ns(x_spec)))
        return _cost_of(fwd, (one, x_sh),
                        (jax.tree.map(ns, ospec), ns(x_spec)), mesh)

    if n_dense:
        comps["layer_dense"] = layer_cost("dense_layers", False)
    if n_moe:
        comps["layer_moe"] = layer_cost("moe_layers", True)

    # ---- head: embed lookup + final norm + logits + CE (+ MTP) -----------
    head_keys = ["embed", "final_norm"] + \
        (["lm_head"] if "lm_head" in pshape else []) + \
        (["mtp"] if "mtp" in pshape else [])
    hshape = {k: pshape[k] for k in head_keys}
    hspec = {k: pspecs[k] for k in head_keys}
    tok_sh = jax.ShapeDtypeStruct((B, S), jnp.int32)
    tok_spec = P(dp, None) if B > 1 else P(None, None)

    def head_fwd(hp, x, tokens, labels):
        xf = tr.rmsnorm(x, hp["final_norm"], cfg.norm_eps)
        head = hp["embed"].T if cfg.tie_embeddings else hp["lm_head"]
        logits = (xf @ head).astype(jnp.float32)
        if train:
            loss = tr._ce(logits, labels, cfg)
            if cfg.mtp_depth and "mtp" in hp:
                h = hp["embed"][tokens]
                nxt = jnp.roll(tokens, -1, axis=1)
                h2 = jnp.concatenate([h, hp["embed"][nxt]], axis=-1) \
                    @ hp["mtp"]["proj"]
                pos = jnp.arange(S)[None, :]
                h2, _ = tr._layer_fwd(hp["mtp"]["block"], cfg, h2, pos, 0,
                                      moe=False)
                loss = loss + 0.3 * tr._ce((h2 @ head).astype(jnp.float32),
                                           jnp.roll(labels, -1, axis=1), cfg)
            return loss
        return logits[:, -1, :]

    if train:
        def head_fn(hp, x, tokens, labels):
            return jax.grad(lambda a, b: head_fwd(a, b, tokens, labels),
                            argnums=(0, 1))(hp, x)
        comps["head"] = _cost_of(
            head_fn, (hshape, x_sh, tok_sh, tok_sh),
            (jax.tree.map(ns, hspec), ns(x_spec), ns(tok_spec), ns(tok_spec)),
            mesh, out_sh=(jax.tree.map(ns, hspec), ns(x_spec)))
    else:
        comps["head"] = _cost_of(
            head_fwd, (hshape, x_sh, tok_sh, tok_sh),
            (jax.tree.map(ns, hspec), ns(x_spec), ns(tok_spec), ns(tok_spec)),
            mesh)

    # ---- optimizer --------------------------------------------------------
    if train:
        from repro.optim import adamw_init, adamw_update
        knobs = LM_TRAIN_KNOBS[arch_id]
        oshape = jax.eval_shape(partial(
            adamw_init, moments_dtype=jnp.dtype(knobs["moments"])), pshape)
        ospecs = {"step": P(), "m": pspecs, "v": pspecs}

        def opt_fn(g, o, p):
            return adamw_update(g, o, p, lr=1e-4)
        gshape = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), pshape)
        comps["opt"] = _cost_of(
            opt_fn, (gshape, oshape, pshape),
            (jax.tree.map(ns, pspecs), jax.tree.map(ns, ospecs),
             jax.tree.map(ns, pspecs)), mesh)

    total = _add(
        _scale(comps.get("layer_dense", _scale(comps["head"], 0.0)),
               n_dense * accum),
        _scale(comps.get("layer_moe", _scale(comps["head"], 0.0)),
               n_moe * accum),
        _scale(comps["head"], accum),
        comps.get("opt", _scale(comps["head"], 0.0)))
    return {"components": comps, "adjusted": total,
            "trips": {"accum": accum, "n_dense": n_dense, "n_moe": n_moe}}
