"""Federated query execution across the shard axis (paper §3.2 + §4).

Each shard device holds its padded triple block. A plan step whose pattern
data lives off-PPN triggers an `all_gather` of candidate matches across the
`shards` axis — the SPMD analogue of a SERVICE call; steps whose data is
PPN-local never communicate. A query fully covered by one shard compiles to a
collective-free program, which is exactly the paper's objective made visible
in the HLO.

The engine is one function. It runs:
  * under jax.vmap(axis_name="shards") — single-device simulation (tests,
    CPU benchmarks);
  * under shard_map on a mesh axis — real distribution (dry-run, production).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partitioner import Partitioning
from repro.engine.local import compact, join_step, join_step_sorted, scan_shard
from repro.engine.planner import PhysicalPlan
from repro.engine.primitives import check_backend

AXIS = "shards"


class CapacityOverflowError(RuntimeError):
    """A static capacity (scan cap, table cap, gather_cap, or merge-join
    window) was exceeded at run time: the result set is truncated. Raised by
    the runners under ``strict=True``; otherwise the condition is reported
    through the returned overflow flag."""


def check_gather_cap(gather_cap) -> None:
    """Validate a gather_cap argument before any tracing happens.

    A non-positive capacity would compact every cross-shard gather down to
    nothing — results would be silently empty/truncated rather than an error
    (the overflow flag fires, but only at run time, per request).
    """
    if gather_cap is None:
        return
    if isinstance(gather_cap, bool) or not isinstance(
            gather_cap, (int, np.integer)) or gather_cap < 1:
        raise ValueError(
            f"gather_cap must be a positive int or None, got {gather_cap!r}")


def check_mesh(mesh, n_shards: int, axis_name: str) -> None:
    """A shard_map engine's shard axis must be a mesh axis of exactly the
    plan's shard count: each device holds one shard block (the kernels read
    `triples[0]`), so a divisor-sized axis would silently drop shards and a
    missing axis would break axis_index/all_gather."""
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has axes {mesh.axis_names}, engine shard "
                         f"axis {axis_name!r} is not one of them")
    if mesh.shape[axis_name] != n_shards:
        raise ValueError(
            f"mesh axis {axis_name!r} has {mesh.shape[axis_name]} devices "
            f"but the plan has {n_shards} shards; shard_map execution "
            "needs exactly one device per shard")


def raise_on_overflow(overflow, query_name: str, path: str) -> None:
    """Shared strict-mode check: one error message for every execution path
    (vmapped / sharded / batched), so callers can match on it."""
    if bool(np.asarray(overflow)):
        raise CapacityOverflowError(
            f"query {query_name!r}: static capacity overflow on the {path} "
            "path — results are truncated; raise the plan's scan/table caps, "
            "gather_cap, or max_per_row")


# ---------------------------------------------------------------------------
# shard construction
# ---------------------------------------------------------------------------

@dataclass
class ShardedKG:
    triples: np.ndarray   # (n_shards, cap, 3) int32, padded with -1
    valid: np.ndarray     # (n_shards, cap) bool
    n_shards: int
    cap: int

    @staticmethod
    def build(part: Partitioning, *, pad_multiple: int = 64,
              min_cap: int = 0) -> "ShardedKG":
        """Materialize per-shard triple blocks: each shard's primary rows
        (`assign_triples`, every triple exactly once) followed by any
        replicated rows (`part.replica_rows`). min_cap lets a caller keep
        the pre-replication block shape so compiled engines stay valid."""
        store = part.catalog.store
        assign = part.assign_triples()
        n = part.n_shards
        extra = part.replica_rows() if part.replicas else {}
        sizes = [int((assign == s).sum()) + len(extra.get(s, ()))
                 for s in range(n)]
        cap = max(8, min_cap,
                  int(np.ceil(max(sizes) / pad_multiple)) * pad_multiple)
        tr = np.full((n, cap, 3), -1, dtype=np.int32)
        va = np.zeros((n, cap), dtype=bool)
        for s in range(n):
            rows = store.triples[assign == s]
            rep = extra.get(s)
            if rep is not None:
                rows = np.concatenate([rows, store.triples[rep]])
            tr[s, :rows.shape[0]] = rows
            va[s, :rows.shape[0]] = True
        return ShardedKG(tr, va, n, cap)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def make_engine(plan: PhysicalPlan, *, join_impl: str = "expand",
                max_per_row: int = 64, gather_cap: int | None = None,
                axis_name: str = AXIS, backend: str = "jnp",
                kernel_blocks=None):
    """Build engine(triples, valid, params) -> (table, mask, overflow).

    join_impl: "expand" — paper-faithful expand-and-filter join;
               "sorted" — beyond-paper sort-merge join (§Perf).
    gather_cap: post-all_gather compaction size (default: keep S*scan_cap).
    backend: "jnp" — dense XLA primitives; "pallas" — fused kg_scan/kg_join
    kernels (bit-identical results; kernel_blocks sets their tile sizes).
    """
    blocks = check_backend(backend, kernel_blocks)
    S = plan.n_shards

    def engine(triples: jax.Array, valid: jax.Array, params: jax.Array):
        my = jax.lax.axis_index(axis_name) if S > 1 else jnp.int32(0)
        table = jnp.full((plan.table_cap, max(1, plan.n_vars)), -1, jnp.int32)
        tmask = jnp.zeros((plan.table_cap,), bool).at[0].set(True)
        overflow = jnp.zeros((), bool)

        for step in plan.steps:
            if step.is_noop:   # bucket padding: identity on the table
                continue
            s_, p_, o_ = (jnp.asarray(v, jnp.int32) for v in step.consts)
            for pos, pidx in step.param_slots:
                val = params[pidx]
                if pos == 0:
                    s_ = val
                elif pos == 1:
                    p_ = val
                else:
                    o_ = val
            m, mm, ovf = scan_shard(triples, valid, s_, p_, o_, step.eqs,
                                    step.scan_cap, backend=backend,
                                    blocks=blocks)
            overflow = overflow | ovf

            if step.gather and S > 1:
                owner = jnp.asarray([i in step.owners for i in range(S)])
                mm = mm & owner[my]
                m_all = jax.lax.all_gather(m, axis_name)     # (S, cap, 3)
                mm_all = jax.lax.all_gather(mm, axis_name)   # (S, cap)
                m = m_all.reshape(S * step.scan_cap, 3)
                mm = mm_all.reshape(S * step.scan_cap)
                if gather_cap is not None and gather_cap < S * step.scan_cap:
                    m, mm, ovf2 = compact(m, mm, gather_cap)
                    overflow = overflow | ovf2

            if join_impl == "sorted":
                table, tmask, ovf3 = join_step_sorted(
                    table, tmask, m, mm, step.shared, step.new,
                    max_per_row=max_per_row, backend=backend, blocks=blocks)
            else:
                table, tmask, ovf3 = join_step(table, tmask, m, mm,
                                               step.shared, step.new,
                                               backend=backend,
                                               blocks=blocks)
            overflow = overflow | ovf3
        return table, tmask, overflow

    return engine


# ---------------------------------------------------------------------------
# runners
# ---------------------------------------------------------------------------

def run_vmapped(plan: PhysicalPlan, kg: ShardedKG,
                params: np.ndarray | None = None, *,
                join_impl: str = "expand", max_per_row: int = 64,
                gather_cap: int | None = None, jit: bool = True,
                strict: bool = False, backend: str = "jnp",
                kernel_blocks=None):
    """Single-device simulation: vmap over the shard axis. Returns the PPN
    device's (solutions, count, overflow); strict=True raises
    CapacityOverflowError instead of returning a truncated result."""
    check_gather_cap(gather_cap)
    engine = make_engine(plan, join_impl=join_impl, max_per_row=max_per_row,
                         gather_cap=gather_cap, backend=backend,
                         kernel_blocks=kernel_blocks)
    p = jnp.zeros((max(1, plan.n_params),), jnp.int32) if params is None \
        else jnp.asarray(params, jnp.int32)
    fn = jax.vmap(engine, in_axes=(0, 0, None), axis_name=AXIS)
    if jit:
        fn = jax.jit(fn)
    table, tmask, overflow = fn(jnp.asarray(kg.triples), jnp.asarray(kg.valid), p)
    res = _extract(plan, table, tmask, overflow)
    if strict:
        raise_on_overflow(res[2], plan.query.name, "vmapped")
    return res


def run_sharded(plan: PhysicalPlan, kg: ShardedKG, mesh,
                params: np.ndarray | None = None, *,
                join_impl: str = "expand", max_per_row: int = 64,
                gather_cap: int | None = None, axis: str | None = None,
                strict: bool = False, backend: str = "jnp",
                kernel_blocks=None):
    """shard_map execution on a real mesh axis (dry-run / production).

    strict=True raises CapacityOverflowError (same error type and message
    format as run_vmapped) instead of returning a truncated result."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import shard_map_compat

    check_gather_cap(gather_cap)
    axis = axis or AXIS
    check_mesh(mesh, plan.n_shards, axis)
    engine = make_engine(plan, join_impl=join_impl, max_per_row=max_per_row,
                         gather_cap=gather_cap, axis_name=axis,
                         backend=backend, kernel_blocks=kernel_blocks)

    def kernel(triples, valid, params):
        t, m, o = engine(triples[0], valid[0], params)
        return t[None], m[None], o[None]

    # no shard_map replication rule exists for pallas_call: skip the checker
    # (not the collectives) on the pallas backend, as in the batched engine
    fn = shard_map_compat(kernel, mesh=mesh,
                          in_specs=(P(axis), P(axis), P()),
                          out_specs=(P(axis), P(axis), P(axis)),
                          check_rep=backend != "pallas")
    p = jnp.zeros((max(1, plan.n_params),), jnp.int32) if params is None \
        else jnp.asarray(params, jnp.int32)
    table, tmask, overflow = jax.jit(fn)(jnp.asarray(kg.triples),
                                         jnp.asarray(kg.valid), p)
    res = _extract(plan, table, tmask, overflow)
    if strict:
        raise_on_overflow(res[2], plan.query.name, "sharded")
    return res


def _extract(plan: PhysicalPlan, table, tmask, overflow):
    """Pull the PPN shard's solutions, dedup, sort (matching the oracle)."""
    # to host first: a mesh-sharded result is not indexable on the device
    t = np.asarray(table)[plan.ppn]
    m = np.asarray(tmask)[plan.ppn]
    ov = bool(np.asarray(overflow)[plan.ppn])
    rows = t[m][:, :plan.n_vars]   # drop the dummy column of 0-var queries
    rows = np.unique(rows, axis=0) if rows.shape[0] \
        else rows.reshape(0, plan.n_vars)
    return rows.astype(np.int32), int(rows.shape[0]), ov


def lower_engine(plan: PhysicalPlan, kg_shape: tuple[int, int], mesh,
                 *, join_impl: str = "expand", max_per_row: int = 64,
                 axis: str = "model"):
    """Lower (not run) the federated engine for a production mesh — used by
    the dry-run to count collective bytes per query plan."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import shard_map_compat

    engine = make_engine(plan, join_impl=join_impl, max_per_row=max_per_row,
                         axis_name=axis)

    def kernel(triples, valid, params):
        t, m, o = engine(triples[0], valid[0], params)
        return t[None], m[None], o[None]

    fn = shard_map_compat(kernel, mesh=mesh,
                          in_specs=(P(axis), P(axis), P()),
                          out_specs=(P(axis), P(axis), P(axis)))
    n, cap = kg_shape
    args = (jax.ShapeDtypeStruct((n, cap, 3), jnp.int32),
            jax.ShapeDtypeStruct((n, cap), jnp.bool_),
            jax.ShapeDtypeStruct((max(1, plan.n_params),), jnp.int32))
    return jax.jit(fn).lower(*args)
