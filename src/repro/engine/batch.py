"""Batched multi-query serving: plan bucketing + one compiled engine per bucket.

The per-query engine (`engine/federated.py`) bakes every plan's structure —
join columns, constants, owner sets — into the traced program, so serving a
workload costs one XLA compile + dispatch per query. This module turns the
plan structure into *data*: plans are padded to shape buckets (same step
count, per-step scan caps, table cap) and their steps are encoded as small
integer tensors, so one compiled engine executes every plan in a bucket and
`jax.vmap` runs a whole batch of (plan, params) requests — an entire workload,
including many user-parameterized instances of each template query — in a
handful of XLA programs.

Per-request runtime data (`PlanData`, one row per plan step):
  consts (L,3)  term id per triple position, -1 wildcard / -2 never-match
  pidx   (L,3)  params-vector index per position, -1 = use the constant
  eq     (L,3)  intra-pattern equality gates for pairs (0,1),(0,2),(1,2)
  kind   (L,3)  0 = unused position, 1 = shared (join) var, 2 = new var
  col    (L,3)  binding-table column of the position's variable
  owner  (L,S)  shards owning the pattern's feature (mask before all_gather)
  noop   (L,)   padding step: the join is computed then discarded (identity)

What stays static lives in the bucket signature and is the compile-cache key:
shard count, step count, table width/cap, per-step scan caps, plus per-step
structure bits that let the trace drop work no member plan needs — `gather`
(any member needs the cross-shard all_gather), `sorted` (every member joins
on a shared variable, so the sort-merge join applies; unlike the per-query
engine it also covers semijoin steps, reporting fan-out beyond max_per_row
through the overflow flag), `eq` / `param` / `noop` (any member uses
intra-pattern equality / runtime params / padding at this step), and
`new_mode` ("all" / "none" / "mixed": whether member steps bind new
variables, which selects the expansion, semijoin, or both join outcomes).

The scan/join primitives themselves live in `engine/primitives` (shared
with the per-query engine) and execute on a pluggable backend: "jnp"
(dense XLA) or "pallas" (fused kernels/kg_scan + kernels/kg_join), chosen
per engine build and keyed into the EngineCache. Results are bit-identical
across backends on every path (vmap, shard_map, adaptive migration).
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.federated import (AXIS, ShardedKG, check_gather_cap,
                                    check_mesh, compact, raise_on_overflow)
from repro.engine.planner import PhysicalPlan, pad_plan
from repro.engine.primitives import (DEFAULT_BLOCKS, EQ_PAIRS, INT_MAX,
                                     KernelBlocks, check_backend,
                                     compat_matrix, join_ranges, rank_sites,
                                     rank_sorted, scan_hits, select_cap,
                                     select_from_cum)

_EQ_PAIRS = EQ_PAIRS   # shared sentinels: one definition, engine/primitives
_INT_MAX = INT_MAX


class PlanData(NamedTuple):
    """Per-plan step structure as arrays (leading batch axis once stacked)."""
    consts: jax.Array   # (..., L, 3) int32
    pidx: jax.Array     # (..., L, 3) int32
    eq: jax.Array       # (..., L, 3) bool
    kind: jax.Array     # (..., L, 3) int32
    col: jax.Array      # (..., L, 3) int32
    owner: jax.Array    # (..., L, S) bool
    noop: jax.Array     # (..., L) bool


@dataclass(frozen=True)
class BucketSignature:
    """Everything the compiled bucket engine specializes on."""
    n_shards: int
    n_steps: int
    n_vars: int                      # binding-table width (>= 1)
    table_cap: int
    scan_caps: tuple[int, ...]
    fanout_caps: tuple[int, ...]     # merge-join window width per step
    verify_masks: tuple[tuple[bool, bool, bool], ...]  # positions any member
                                     # verifies as a 2nd+ shared column
    gather_bits: tuple[bool, ...]
    sorted_bits: tuple[bool, ...]
    eq_bits: tuple[bool, ...]
    param_bits: tuple[bool, ...]
    noop_bits: tuple[bool, ...]
    new_modes: tuple[str, ...]       # "all" | "none" | "mixed"


def bucket_collectives(sig: BucketSignature) -> int:
    """Number of gather sites the bucket engine traces: one per step where
    any member plan's pattern owners are not covered by its PPN. Under
    shard_map each site lowers to all_gather collectives (two ops: matches +
    mask); under vmap simulation the same sites lower to collective-free
    reshapes. The WawPart objective (minimize partition cuts) is exactly
    minimizing this count."""
    if sig.n_shards <= 1:
        return 0
    return sum(1 for g in sig.gather_bits if g)


def count_hlo_collectives(text: str) -> int:
    """Count all_gather/all_reduce ops in lowered StableHLO text (from
    ``jitted.lower(...).as_text()``) — the verification side of the
    collective-count-as-cut-count invariant: for a sharded bucket engine this
    equals 2 * bucket_collectives(sig) (matches + mask per gather site); for
    the vmap simulation it is 0 (the same gathers lower to reshapes)."""
    return (text.count("stablehlo.all_gather")
            + text.count("stablehlo.all_reduce"))


@dataclass
class PlanBucket:
    """One shape bucket: a signature plus the member plans padded to it.

    ``plans`` are the bucket's members (noop-padded to ``signature.n_steps``),
    ``n_params`` the widest member's params vector (requests zero-pad to it),
    and ``pdata`` the per-plan numpy ``PlanData`` the engine consumes.
    """

    signature: BucketSignature
    plans: list[PhysicalPlan]        # padded to the signature's shape
    n_params: int                    # params-vector width (>= 1)
    pdata: list[PlanData] = field(default_factory=list)  # per-plan, numpy


def _plan_data(plan: PhysicalPlan, sig: BucketSignature) -> PlanData:
    L, S = sig.n_steps, sig.n_shards
    consts = np.full((L, 3), -2, np.int32)
    pidx = np.full((L, 3), -1, np.int32)
    eq = np.zeros((L, 3), bool)
    kind = np.zeros((L, 3), np.int32)
    col = np.zeros((L, 3), np.int32)
    owner = np.zeros((L, S), bool)
    noop = np.zeros((L,), bool)
    for i, step in enumerate(plan.steps):
        if step.is_noop:
            noop[i] = True
            continue
        consts[i] = step.consts
        for pos, p_i in step.param_slots:
            pidx[i, pos] = p_i
        for k, pair in enumerate(_EQ_PAIRS):
            if pair in step.eqs:
                eq[i, k] = True
        for pos, c_ in step.shared:
            kind[i, pos], col[i, pos] = 1, c_
        for pos, c_ in step.new:
            kind[i, pos], col[i, pos] = 2, c_
        for s in step.owners:
            owner[i, s] = True
    return PlanData(consts, pidx, eq, kind, col, owner, noop)


def _pad_level(n: int, levels: tuple[int, ...]) -> int:
    for lvl in levels:
        if n <= lvl:
            return lvl
    return n  # longer than every level: its own bucket size


DEFAULT_STEP_LEVELS = (1, 2, 3, 4, 6, 8, 12, 16)


def bucket_plans(plans: list[PhysicalPlan], *,
                 step_levels: tuple[int, ...] = DEFAULT_STEP_LEVELS,
                 ) -> list[PlanBucket]:
    """Group plans into shape buckets and pad members to the bucket shape.

    Plans are grouped by (n_shards, step count rounded up to a level); within
    a group, per-step scan caps, the table cap, and the table width are lifted
    to the group maximum, which *is* the bucket signature — identical
    signatures from different workloads share one compiled engine.
    """
    groups: dict[tuple[int, int], list[PhysicalPlan]] = {}
    for p in plans:
        key = (p.n_shards, _pad_level(len(p.steps), step_levels))
        groups.setdefault(key, []).append(p)

    buckets: list[PlanBucket] = []
    for (S, L), members in sorted(groups.items()):
        scan_caps, fanout_caps, gather_bits, sorted_bits = [], [], [], []
        eq_bits, param_bits, noop_bits, new_modes = [], [], [], []
        verify_masks = []
        for i in range(L):
            steps = [p.steps[i] for p in members if i < len(p.steps)]
            real = [s for s in steps if not s.is_noop]  # members may arrive
            # pre-padded (pad_plan); their no-op steps must not shape the
            # structure bits, only the capacity maxima
            scan_caps.append(max([s.scan_cap for s in steps] or [8]))
            fanout_caps.append(max([s.block_fanout_cap for s in real] or [8]))
            vm = [False, False, False]
            for s in real:
                for pos, _ in s.shared[1:]:
                    vm[pos] = True
            verify_masks.append(tuple(vm))
            gather_bits.append(any(s.gather for s in real))
            sorted_bits.append(bool(real) and all(s.shared for s in real))
            eq_bits.append(any(s.eqs for s in real))
            param_bits.append(any(s.param_slots for s in real))
            noop_bits.append(len(real) < len(members))
            with_new = sum(1 for s in real if s.new)
            new_modes.append("all" if real and with_new == len(real) else
                             "none" if with_new == 0 else "mixed")
        n_vars = max(1, max(p.n_vars for p in members))
        table_cap = max(p.table_cap for p in members)
        sig = BucketSignature(
            n_shards=S, n_steps=L, n_vars=n_vars, table_cap=table_cap,
            scan_caps=tuple(scan_caps), fanout_caps=tuple(fanout_caps),
            verify_masks=tuple(verify_masks), gather_bits=tuple(gather_bits),
            sorted_bits=tuple(sorted_bits), eq_bits=tuple(eq_bits),
            param_bits=tuple(param_bits), noop_bits=tuple(noop_bits),
            new_modes=tuple(new_modes))
        padded = [pad_plan(p, L, scan_caps=scan_caps, table_cap=table_cap)
                  for p in members]
        n_params = max(1, max(p.n_params for p in members))
        bucket = PlanBucket(signature=sig, plans=padded, n_params=n_params)
        bucket.pdata = [_plan_data(p, sig) for p in padded]
        buckets.append(bucket)
    return buckets


# ---------------------------------------------------------------------------
# data-driven engine primitives
# ---------------------------------------------------------------------------

_select_cap = select_cap   # one implementation: engine/primitives (shared
                           # with the per-query engine and the kernel refs)


def _materialize(triples, hit, cum, cap: int):
    """Compact matching rows to (min(cap, N), 3) in shard order — when the
    static cap covers the whole shard the selection (and the overflow
    reduction) is dropped from the trace entirely. `cum` is the hit mask's
    inclusive prefix sum — jnp.cumsum on the jnp backend, the fused kg_scan
    kernel output on the pallas backend (unused when the cap covers the
    shard; XLA drops the dead jnp cumsum)."""
    if cap >= triples.shape[0]:
        return triples, hit, jnp.zeros((), bool)
    idx, mm, total = select_from_cum(cum, cap)
    return triples[idx], mm, total > cap


def shard_perms(kg: ShardedKG) -> np.ndarray:
    """(S, 3, N) int32: per shard, the stable sort permutation of its triple
    block by each triple position. The batched sort-merge join materializes
    matches through the join-key position's permutation, so its keys are
    sorted *by construction* — XLA:CPU runs sort at ~200ns/element, so a
    per-step runtime sort would dominate the whole engine."""
    S, N = kg.n_shards, kg.cap
    perms = np.empty((S, 3, N), np.int32)
    for s in range(S):
        for pos in range(3):
            perms[s, pos] = np.argsort(kg.triples[s, :, pos], kind="stable")
    return perms


def _select_windows(n, width: int, cap: int):
    """`select_cap` over a flat mask of windows of `width` slots whose
    window g holds n[g] leading set slots, without materializing it: the
    same (idx, sel, total), from a prefix sum over the windows."""
    cum = jnp.cumsum(n)
    total = cum[-1]
    j = jnp.arange(cap, dtype=cum.dtype)
    g, = rank_sorted(cum, j, "right")
    g = jnp.clip(g, 0, n.shape[0] - 1)
    sel = j < total
    idx = jnp.where(sel, g * width + j - (cum[g] - n[g]),
                    n.shape[0] * width - 1)
    return idx, sel, total


def _select_rows(mask, cap: int):
    """`select_cap` over mask.T.reshape(-1) — a (W, R) mask read row by
    row — without the transpose, whose (R, W) layout pads W to a full lane
    tile: a prefix sum over the rows picks each slot's row, one down the
    row's W slots picks its position. Same (idx, sel, total)."""
    W, R = mask.shape
    wcum = jnp.cumsum(mask.astype(jnp.int32), axis=0)     # (W, R)
    n = wcum[-1]
    cum = jnp.cumsum(n)
    total = cum[-1]
    j = jnp.arange(min(cap, W * R), dtype=cum.dtype)
    g, = rank_sorted(cum, j, "right")
    g = jnp.clip(g, 0, R - 1)
    t = j - (cum[g] - n[g])                    # rank of slot j in row g
    w = jnp.sum(wcum[:, g] <= t[None, :], axis=0, dtype=cum.dtype)
    sel = j < total
    return jnp.where(sel, g * W + w, W * R - 1), sel, total


def _materialize_view(triples, perms, hit, pos0, cap: int):
    """Compact matching rows to (min(cap, N), 3), ordered by the pos0 column
    (via the precomputed per-position sort permutations), valid rows first —
    so the pos0 keys of the valid prefix are sorted."""
    perm = perms[pos0]                       # (N,) — runtime-selected view
    idx, mm, total = _select_cap(hit[perm], min(cap, perm.shape[0]))
    m = triples[perm[idx]]
    ovf = (total > cap) if cap < perm.shape[0] else jnp.zeros((), bool)
    return m, mm, ovf


def _scatter_new(out, values, kind, col, n_vars: int):
    """Write matched values into their (runtime-chosen) new-var columns."""
    colids = jnp.arange(n_vars)[None, :]
    for pos in range(3):
        hot = (kind[pos] == 2) & (colids == jnp.clip(col[pos], 0, n_vars - 1))
        out = jnp.where(hot, values[pos][:, None], out)
    return out


def _mix(new_mode: str, kind, expansion, semijoin):
    """Select the (table, mask, overflow) outcome per the bucket's new_mode."""
    if new_mode == "all":
        return expansion()
    if new_mode == "none":
        return semijoin()
    te, me, oe = expansion()
    ts, ms, os_ = semijoin()
    has_new = jnp.any(kind == 2)
    return (jnp.where(has_new, te, ts), jnp.where(has_new, me, ms),
            jnp.where(has_new, oe, os_))


def _seed_join(table, matches, mmask, kind, col, new_mode: str):
    """Step-0 join: the table holds only the seed row, so the 'join' is a
    compaction of the matches straight into the table columns — avoids the
    R x C compat matrix exactly where C is largest (unselective first scans).
    Bit-equivalent to the general joins on a seed table."""
    R, V = table.shape

    def expansion():
        if matches.shape[0] <= R:        # matches fit: no selection needed
            m, mm = matches, mmask
            ovf = jnp.zeros((), bool)
        else:
            idx, mm, total = _select_cap(mmask, R)
            m = matches[idx]
            ovf = total > R
        if m.shape[0] < R:
            m = jnp.pad(m, ((0, R - m.shape[0]), (0, 0)), constant_values=-1)
            mm = jnp.pad(mm, (0, R - mm.shape[0]))
        out = _scatter_new(jnp.full((R, V), -1, jnp.int32),
                           [m[:, pos] for pos in range(3)], kind, col, V)
        return out, mm, ovf

    def semijoin():                      # fully-constant first pattern
        return (table, jnp.zeros((R,), bool).at[0].set(jnp.any(mmask)),
                jnp.zeros((), bool))

    return _mix(new_mode, kind, expansion, semijoin)


def _join_data(table, tmask, matches, mmask, kind, col, new_mode: str, *,
               backend: str = "jnp", blocks: KernelBlocks = DEFAULT_BLOCKS):
    """Expand-and-filter join with the join structure as runtime data. The
    R x C compatibility matrix comes from the shared primitive (dense jnp
    or the tiled kg_join kernel); the expansion/semijoin epilogues are
    backend-independent."""
    R, V = table.shape
    C = matches.shape[0]
    compat = compat_matrix(table, tmask, matches, mmask, kind, col,
                           backend=backend, blocks=blocks)

    def expansion():
        flat = compat.reshape(-1)
        order, omask, total = _select_cap(flat, R)
        r_idx, c_idx = order // C, order % C
        out = _scatter_new(table[r_idx],
                           [matches[c_idx, pos] for pos in range(3)],
                           kind, col, V)
        return out, omask, total > R

    def semijoin():
        return table, tmask & compat.any(axis=1), jnp.zeros((), bool)

    return _mix(new_mode, kind, expansion, semijoin)


def _join_merge(table, tmask, m_blocks, mm_blocks, pos0, kind, col,
                new_mode: str, *, max_per_row: int,
                verify_mask: tuple[bool, bool, bool],
                backend: str = "jnp",
                blocks: KernelBlocks = DEFAULT_BLOCKS):
    """Merge join against per-shard match blocks whose pos0 keys are sorted
    (valid prefix) by construction — a rank search per block locates each
    table row's candidate range, up to max_per_row candidates *per block* are
    expanded, and the remaining shared columns verify during expansion. No
    sort appears anywhere. Only traced for steps where every bucket member
    joins on a shared var; fan-out beyond max_per_row sets the overflow flag.

    m_blocks: (S_b, C, 3), mm_blocks: (S_b, C) — one block per gathered
    shard, or a single block for PPN-local steps. verify_mask flags the
    positions some member verifies as a 2nd+ shared column: only those
    force an S_b*K x R candidate mask and its gathers before selection;
    without them the surviving candidates of each (row, block) window are
    its first min(count, K) slots, and selection runs over the R x S_b
    window counts. Candidate values are gathered after selection, R at a
    time (R and K both grow with the graph: an R x S_b*K intermediate
    grows with its square).
    """
    R, V = table.shape
    Sb, C = mm_blocks.shape
    K = min(max_per_row, C)
    is_sh = kind == 1
    col0 = jnp.clip(col[jnp.argmax(is_sh)], 0, V - 1)

    keys = jnp.where(mm_blocks, jnp.take(m_blocks, pos0, axis=2), _INT_MAX)
    rkey = jnp.take(table, col0, axis=1)
    lo, hi = join_ranges(keys, rkey, backend=backend, blocks=blocks)
    counts = jnp.where(tmask[None, :], hi - lo, 0)       # (S_b, R)
    overflow_fanout = jnp.max(counts) > K

    m_flat = m_blocks.reshape(Sb * C, 3)

    def cand_idx(order):
        """Flat indices into m_flat for pair slots `order` (any shape)."""
        blk = (order % (Sb * K)) // K
        within = order % K
        row = order // (Sb * K)
        src = jnp.clip(lo[blk, row] + within, 0, C - 1)
        return blk * C + src

    if any(verify_mask):
        # a 2nd+ shared column can reject any candidate, so the survivors
        # of a (row, block) window are no longer its prefix: check them
        # all, laid out (S_b, K, R) so rows run along the minor axis
        offs = jnp.arange(K)[None, :, None]
        ok = offs < counts[:, None, :]                  # tmask is in counts
        src = (jnp.clip(lo[:, None, :] + offs, 0, C - 1)
               + (jnp.arange(Sb) * C)[:, None, None])   # m_flat rows
        for pos in range(3):
            if not verify_mask[pos]:
                continue
            chk = is_sh[pos] & (pos != pos0)
            cc = jnp.clip(col[pos], 0, V - 1)
            ok = ok & jnp.where(
                chk, m_flat[:, pos][src] == jnp.take(table, cc, axis=1),
                True)
        def select():                                  # (row, block, k)
            return _select_rows(ok.reshape(Sb * K, R), R)
        live = ok.any(axis=(0, 1))
    else:
        # the survivors of each (row, block) window are its first
        # min(count, K) slots: select over the (R, S_b) window sizes, not
        # over an R x S_b*K pair mask (same pairs, same order)
        def select():
            return _select_windows(jnp.minimum(counts, K).T.reshape(-1), K,
                                   R)
        live = jnp.any(counts > 0, axis=0)

    def expansion():
        # select surviving (row, candidate) pairs first, THEN gather their
        # match values — R gathers instead of R*S_b*K
        order, omask, total = select()
        vals = m_flat[cand_idx(order)]               # (R, 3)
        out = _scatter_new(table[order // (Sb * K)],
                           [vals[:, pos] for pos in range(3)], kind, col, V)
        return out, omask, total > R

    def semijoin():
        return table, tmask & live, jnp.zeros((), bool)

    t2, m2, ovf = _mix(new_mode, kind, expansion, semijoin)
    return t2, m2, ovf | overflow_fanout


# ---------------------------------------------------------------------------
# bucket engine
# ---------------------------------------------------------------------------

def program_name(sig: BucketSignature, backend: str = "jnp") -> str:
    """The bucket program's name, from its signature: ``kg_L<steps>_V<table
    width>_R<table cap>``, plus ``_<backend>`` off the jnp backend. Its jit
    module is ``jit_<name>``, the same on every run, so a profile says
    which bucket a device op belongs to."""
    name = f"kg_L{sig.n_steps}_V{sig.n_vars}_R{sig.table_cap}"
    return name if backend == "jnp" else f"{name}_{backend}"


def _named(fn, name: str):
    """`fn` renamed, so that jit names its module after `name`."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def make_batched_engine(sig: BucketSignature, *, join_impl: str = "expand",
                        max_per_row: int | None = None,
                        gather_cap: int | None = None,
                        axis_name: str = AXIS, backend: str = "jnp",
                        kernel_blocks: KernelBlocks | None = None):
    """Build engine(triples, valid, perms, pdata, params) ->
    (table, mask, overflow) for one bucket signature. The engine is
    plan-agnostic: every member plan of any bucket with this signature runs
    through the same traced program. `perms` comes from `shard_perms(kg)`.

    gather_cap (post-all_gather compaction) applies to the expand/base join
    path; the merge join keeps gathered matches in per-shard blocks, whose
    size is already bounded by the step's scan cap.

    max_per_row: ceiling on the merge-join window width. The per-step width
    is the signature's data-sized fanout cap — one unselective join (LUBM Q8
    dept->students) must not widen every other step's window; pass an int
    only to clamp it further (risking overflow, which the flag reports).

    backend: "jnp" executes the scan/join primitives as dense XLA ops;
    "pallas" routes the pattern scan (fused predicate + hit-count prefix
    sum) through kernels/kg_scan and the join kernels (candidate-range
    search, compat matrix) through kernels/kg_join, bit-identically —
    engine composition (vmap batching, shard_map collectives, overflow
    flags) is backend-independent. kernel_blocks sets the kernels' tile
    sizes (a compile-cache key; see EngineCache).

    The engine's `rank_sites` dict (carried by EngineCache's jitted
    engines too) counts its rank searches by the method
    `primitives.rank_method` picks on the default backend; it fills when
    the engine is traced.
    """
    check_gather_cap(gather_cap)
    blocks = check_backend(backend, kernel_blocks)
    S, L, V, R = sig.n_shards, sig.n_steps, sig.n_vars, sig.table_cap
    sites: dict[str, int] = {}

    def engine(triples: jax.Array, valid: jax.Array, perms: jax.Array,
               pd: PlanData, params: jax.Array):
        """One request's plan interpreted against the (sharded) KG; each
        trace records its rank searches' methods in `rank_sites`."""
        with rank_sites() as traced:
            out = interpret(triples, valid, perms, pd, params)
        sites.clear()
        sites.update(traced)
        return out

    def interpret(triples, valid, perms, pd, params):
        my = jax.lax.axis_index(axis_name) if S > 1 else jnp.int32(0)
        table = jnp.full((R, V), -1, jnp.int32)
        tmask = jnp.zeros((R,), bool).at[0].set(True)
        overflow = jnp.zeros((), bool)
        N = triples.shape[0]

        for i in range(L):
            cap = sig.scan_caps[i]
            merge = (i > 0 and join_impl == "sorted" and sig.sorted_bits[i])
            gather = sig.gather_bits[i] and S > 1
            with jax.named_scope(f"step{i}/scan"):
                spo = pd.consts[i]
                if sig.param_bits[i]:
                    spo = jnp.where(pd.pidx[i] >= 0,
                                    params[jnp.clip(pd.pidx[i], 0)], spo)
                eq = pd.eq[i] if sig.eq_bits[i] else None
                va = valid
                if gather:
                    # owner gate folded into the validity mask so the fused
                    # scan's hit-count already reflects it (== hit & owner)
                    va = va & pd.owner[i, my]
                if merge:   # matches per block, pos0-sorted by construction
                    pos0 = jnp.argmax(pd.kind[i] == 1)
                    if backend == "pallas":
                        # scan the permuted view directly: the kernel's
                        # fused hit-count is then the compaction cumsum for
                        # the sorted-by-construction block (rowwise
                        # predicate commutes with the permutation)
                        perm = perms[pos0]
                        tp = triples[perm]
                        _, cum = scan_hits(tp, va[perm], spo, eq,
                                           backend=backend, blocks=blocks)
                        idx, mm, total = select_from_cum(cum, min(cap, N))
                        m = tp[idx]
                        step_ovf = (total > cap) if cap < N \
                            else jnp.zeros((), bool)
                    else:
                        hit, _ = scan_hits(triples, va, spo, eq)
                        m, mm, step_ovf = _materialize_view(
                            triples, perms, hit, pos0, cap)
                else:
                    hit, cum = scan_hits(triples, va, spo, eq,
                                         backend=backend, blocks=blocks)
                    m, mm, step_ovf = _materialize(triples, hit, cum, cap)
            with jax.named_scope(f"step{i}/gather"):
                if merge:
                    if gather:
                        m = jax.lax.all_gather(m, axis_name)    # (S, C, 3)
                        mm = jax.lax.all_gather(mm, axis_name)  # (S, C)
                    else:
                        m, mm = m[None], mm[None]
                elif gather:
                    C = m.shape[0]
                    m = jax.lax.all_gather(m, axis_name).reshape(S * C, 3)
                    mm = jax.lax.all_gather(mm, axis_name).reshape(S * C)
                    if gather_cap is not None and gather_cap < S * C:
                        m, mm, ovf_g = compact(m, mm, gather_cap)
                        step_ovf = step_ovf | ovf_g
            with jax.named_scope(f"step{i}/join"):
                if merge:
                    K = sig.fanout_caps[i] if max_per_row is None \
                        else min(max_per_row, sig.fanout_caps[i])
                    t2, m2, ovf_j = _join_merge(
                        table, tmask, m, mm, pos0, pd.kind[i], pd.col[i],
                        sig.new_modes[i], max_per_row=K,
                        verify_mask=sig.verify_masks[i], backend=backend,
                        blocks=blocks)
                elif i == 0:
                    t2, m2, ovf_j = _seed_join(table, m, mm, pd.kind[i],
                                               pd.col[i], sig.new_modes[i])
                else:
                    t2, m2, ovf_j = _join_data(table, tmask, m, mm,
                                               pd.kind[i], pd.col[i],
                                               sig.new_modes[i],
                                               backend=backend,
                                               blocks=blocks)
                if sig.noop_bits[i]:         # some member pads here: gate
                    noop = pd.noop[i]
                    table = jnp.where(noop, table, t2)
                    tmask = jnp.where(noop, tmask, m2)
                    overflow = overflow | (~noop & (step_ovf | ovf_j))
                else:
                    table, tmask = t2, m2
                    overflow = overflow | step_ovf | ovf_j
        return table, tmask, overflow

    engine.rank_sites = sites
    return _named(engine, program_name(sig, backend))


def make_sharded_batched_engine(sig: BucketSignature, mesh, *,
                                join_impl: str = "expand",
                                max_per_row: int | None = None,
                                gather_cap: int | None = None,
                                axis_name: str = AXIS,
                                backend: str = "jnp",
                                kernel_blocks: KernelBlocks | None = None):
    """shard_map counterpart of the vmapped bucket engine: same call shape
    fn(triples, valid, perms, pdata, params) -> (table, mask, overflow) with
    a (batch, shard, ...) result layout, but the shard axis is a real mesh
    axis — KG tensors live one block per device (sharding.rules.kg_specs),
    scans/joins run shard-locally, and only the plan steps whose owner
    metadata marks a partition cut emit all_gather collectives. Batch
    vmapping happens *inside* the shard_map kernel, so per-device programs
    stay single-dispatch per bucket per batch.
    """
    from repro.sharding.rules import (kg_out_specs, kg_specs,
                                      shard_map_compat)

    check_mesh(mesh, sig.n_shards, axis_name)
    engine = make_batched_engine(sig, join_impl=join_impl,
                                 max_per_row=max_per_row,
                                 gather_cap=gather_cap, axis_name=axis_name,
                                 backend=backend,
                                 kernel_blocks=kernel_blocks)

    def kernel(triples, valid, perms, pd, params):
        """Per-shard body: vmap the engine over the batch axis."""
        t, m, o = jax.vmap(engine, in_axes=(None, None, None, 0, 0))(
            triples[0], valid[0], perms[0], pd, params)
        return t[None], m[None], o[None]

    # the shard_map replication checker has no rule for pallas_call; the
    # pallas engine is per-shard SPMD like the jnp one, so skipping the
    # check (not the collectives) is sound — jnp keeps the checked path
    sm = shard_map_compat(kernel, mesh=mesh, in_specs=kg_specs(axis_name),
                          out_specs=kg_out_specs(axis_name),
                          check_rep=backend != "pallas")

    def fn(triples, valid, perms, pd, params):
        """shard_map the kernel and restore the vmap path's axis order."""
        t, m, o = sm(triples, valid, perms, pd, params)
        # (shard, batch, ...) -> (batch, shard, ...): match the vmap path's
        # layout so extract_batch serves both
        return (jnp.swapaxes(t, 0, 1), jnp.swapaxes(m, 0, 1),
                jnp.swapaxes(o, 0, 1))

    jitted = jax.jit(_named(fn, engine.__name__))
    jitted.rank_sites = engine.rank_sites
    return jitted


class EngineCache:
    """Compile cache: one jitted bucket engine per (signature, options).

    `misses` counts engine builds — the bench's "compile count ≤ number of
    buckets" check reads it (jax.jit re-specializes internally per batch
    shape, which the steady-state serving loop never changes). A mesh keys
    the shard_map variant: vmapped and sharded engines for one signature are
    distinct programs and cache side by side. The execution backend and its
    kernel tile sizes key the cache the same way: a jnp engine and a pallas
    engine for one signature — or two pallas engines with different
    KernelBlocks — are distinct compiled programs and must never collide.

    `capacity` bounds the cache with LRU eviction (a drifting workload
    can mint unboundedly many bucket signatures across migrations —
    compiled-engine memory must not grow without limit); ``None`` keeps
    the historical unbounded behavior. `evictions` counts engines
    dropped; the serving layer republishes it into the obs registry
    (`engine_cache_evictions`).
    """

    def __init__(self, capacity: int | None = None) -> None:
        if capacity is not None and capacity < 1:
            raise ValueError(f"EngineCache capacity must be >= 1 or None, "
                             f"got {capacity}")
        self._fns: OrderedDict = OrderedDict()
        self.capacity = capacity
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, sig: BucketSignature, *, join_impl: str = "expand",
            max_per_row: int | None = None, gather_cap: int | None = None,
            axis_name: str = AXIS, mesh=None, backend: str = "jnp",
            kernel_blocks: KernelBlocks | None = None):
        """Return the jitted engine for ``(sig, options)``, building on miss.

        ``mesh=None`` returns the double-vmapped simulation engine; a mesh
        returns the shard_map engine for that mesh. ``backend`` and
        ``kernel_blocks`` select the execution backend and its tile sizes
        (validated here via ``check_backend`` — raises ValueError on an
        unknown backend or a non-``KernelBlocks`` tiling). Every argument
        is part of the cache key; `hits`/`misses` count lookups, and a
        hit refreshes the entry's LRU position when the cache is capped.
        """
        blocks = check_backend(backend, kernel_blocks)
        key = (sig, join_impl, max_per_row, gather_cap, axis_name, mesh,
               backend, blocks)
        fn = self._fns.get(key)
        if fn is None:
            self.misses += 1
            if mesh is not None:
                fn = make_sharded_batched_engine(
                    sig, mesh, join_impl=join_impl, max_per_row=max_per_row,
                    gather_cap=gather_cap, axis_name=axis_name,
                    backend=backend, kernel_blocks=blocks)
            else:
                engine = make_batched_engine(
                    sig, join_impl=join_impl, max_per_row=max_per_row,
                    gather_cap=gather_cap, axis_name=axis_name,
                    backend=backend, kernel_blocks=blocks)
                fn = jax.jit(jax.vmap(
                    jax.vmap(engine, in_axes=(0, 0, 0, None, None),
                             axis_name=axis_name),           # shard axis
                    in_axes=(None, None, None, 0, 0)))       # batch axis
                fn.rank_sites = engine.rank_sites
            self._fns[key] = fn
            while self.capacity is not None \
                    and len(self._fns) > self.capacity:
                self._fns.popitem(last=False)
                self.evictions += 1
        else:
            self.hits += 1
            self._fns.move_to_end(key)
        return fn

    def __len__(self) -> int:
        """Compiled engines currently held."""
        return len(self._fns)

    def __bool__(self) -> bool:
        """Always truthy: an empty cache is still a cache (``__len__``
        would otherwise make `cache or EngineCache()` drop a fresh one)."""
        return True


def cost_dict(compiled) -> dict:
    """A compiled program's XLA ``cost_analysis`` properties dict."""
    return compiled.cost_analysis()


# ---------------------------------------------------------------------------
# batch assembly + execution
# ---------------------------------------------------------------------------

def canonical_params(pv: np.ndarray | None, n_params: int) -> bytes:
    """The padded param vector a request executes with, as hashable bytes.

    `assemble_batch` zero-pads every request to the bucket's n_params, so
    `[5]`, `[5, 0]` and — when n_params is 0 — `None` all execute
    identically; canonicalizing here keeps dedup and the answer cache keyed
    on what actually runs. Raises ValueError on vectors longer than the
    bucket width (they cannot execute at all)."""
    vec = np.zeros((n_params,), np.int32)
    if pv is not None:
        pv = np.asarray(pv, np.int32).reshape(-1)
        if pv.shape[0] > n_params:
            raise ValueError(
                f"request has {pv.shape[0]} params but the bucket executes "
                f"with n_params={n_params}; extra values would be dropped")
        vec[:pv.shape[0]] = pv
    return vec.tobytes()


def pad_requests_pow2(requests: list[tuple[int, np.ndarray | None]],
                      ) -> list[tuple[int, np.ndarray | None]]:
    """Pad a request batch to a power-of-two length with noop fillers.

    Per-bucket batch sizes vary with the stream's phase, with how many
    duplicates dedup collapsed, and — under the continuous-batching
    pipeline — with when a deadline cut the bucket queue. Every new
    batch-axis length would be a fresh jit specialization (a recompile in
    steady state), so both the synchronous ``serve()`` path and the
    pipeline's partial-bucket flushes pad the batch axis to the next power
    of two with ``(plan 0, no params)`` filler requests. Fillers sit at the
    tail: extraction truncates to the real requests before the host-side
    ``np.unique``, so the fillers are never observable in results.
    """
    n_pad = 1 << max(0, len(requests) - 1).bit_length()
    return requests + [(0, None)] * (n_pad - len(requests))


def stage_batch(bucket: PlanBucket,
                requests: list[tuple[int, np.ndarray | None]], *,
                mesh=None) -> tuple[PlanData, jnp.ndarray]:
    """Assemble a request batch and start its host-to-device transfer.

    ``assemble_batch`` + ``jax.device_put``: the returned ``(PlanData,
    params)`` are device arrays whose copies are already in flight when the
    engine call is issued, so a serving pipeline can overlap host-side
    param extraction and staging of batch *k+1* with device compute of
    batch *k* (double buffering — JAX dispatch is asynchronous, so the
    caller only blocks when it extracts results). Under a ``mesh`` the
    arrays are placed replicated across the shard axis, matching the
    shard_map engines' ``P()`` in_specs for plan data and params.

    Raises ValueError (from ``assemble_batch``) on an empty batch or on a
    param vector wider than the bucket's ``n_params``.
    """
    pd, params = assemble_batch(bucket, requests)
    if mesh is None:
        return jax.device_put((pd, params))
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put((pd, params),
                          NamedSharding(mesh, PartitionSpec()))


def assemble_batch(bucket: PlanBucket,
                   requests: list[tuple[int, np.ndarray | None]],
                   ) -> tuple[PlanData, jnp.ndarray]:
    """Stack (plan_idx, params) requests into (PlanData[B,...], params[B,P]).

    Raises ValueError on an empty request list or (via
    ``canonical_params``) on a param vector wider than the bucket width.
    """
    if not requests:
        raise ValueError("empty request batch")
    P = bucket.n_params
    stacked = PlanData(*(jnp.asarray(np.stack(
        [getattr(bucket.pdata[idx], f) for idx, _ in requests]))
        for f in PlanData._fields))
    pvecs = np.empty((len(requests), P), np.int32)
    for r, (_, pv) in enumerate(requests):
        pvecs[r] = np.frombuffer(canonical_params(pv, P), np.int32)
    return stacked, jnp.asarray(pvecs)


def fetch_outputs(table, tmask, overflow
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """An engine call's (table, mask, overflow) as host arrays: the
    device-to-host copy of the whole padded batch, every shard (a no-op
    on arrays already on the host)."""
    return np.asarray(table), np.asarray(tmask), np.asarray(overflow)


def extract_batch(bucket: PlanBucket,
                  requests: list[tuple[int, np.ndarray | None]],
                  table, tmask, overflow):
    """Per-request (solutions, count, overflow), PPN shard, sorted + deduped
    (mirrors federated._extract so results compare bit-identically).

    Takes host arrays, as `fetch_outputs` returns them."""
    out = []
    for r, (idx, _) in enumerate(requests):
        plan = bucket.plans[idx]
        t = table[r, plan.ppn]
        m = tmask[r, plan.ppn]
        ov = bool(overflow[r, plan.ppn])
        rows = t[m][:, :plan.n_vars]
        rows = np.unique(rows, axis=0) if rows.shape[0] \
            else rows.reshape(0, plan.n_vars)
        out.append((rows.astype(np.int32), int(rows.shape[0]), ov))
    return out


def dedup_requests(requests: list[tuple[int, np.ndarray | None]],
                   n_params: int | None = None,
                   ) -> tuple[list[tuple[int, np.ndarray | None]], list[int]]:
    """Collapse identical (plan, params) requests to one scanned instance.

    Returns (unique, inverse) with requests[i] equivalent to
    unique[inverse[i]] — the engine executes only the unique instances and
    results fan back out at delivery (extract_fanout). A workload stream of
    many users issuing the same template instance pays for one scan.

    n_params (the bucket width) keys requests on their *padded* param
    vector, so `[5]` and `[5, 0]` — identical once assemble_batch zero-pads
    them — collapse too; without it only byte-identical vectors match."""
    seen: dict[tuple[int, bytes | None], int] = {}
    unique: list[tuple[int, np.ndarray | None]] = []
    inverse: list[int] = []
    for idx, pv in requests:
        if n_params is None:
            raw = None if pv is None else np.asarray(pv, np.int32).tobytes()
            key = (idx, raw)
        else:
            key = (idx, canonical_params(pv, n_params))
        j = seen.get(key)
        if j is None:
            j = seen[key] = len(unique)
            unique.append((idx, pv))
        inverse.append(j)
    return unique, inverse


def extract_fanout(bucket: PlanBucket, unique, inverse: list[int],
                   table, tmask, overflow):
    """extract_batch on the unique instances, fanned back to request order.

    The per-unique host-side work (np.unique dedup/sort) also runs once per
    instance, not once per request."""
    res = extract_batch(bucket, unique, table, tmask, overflow)
    return [res[j] for j in inverse]


def run_batched(bucket: PlanBucket, kg: ShardedKG,
                requests: list[tuple[int, np.ndarray | None]] | None = None,
                *, join_impl: str = "expand", max_per_row: int | None = None,
                gather_cap: int | None = None, cache: EngineCache | None = None,
                perms: np.ndarray | None = None, mesh=None,
                dedup: bool = False, strict: bool = False,
                backend: str = "jnp",
                kernel_blocks: KernelBlocks | None = None):
    """Execute a batch of requests against one bucket.

    mesh=None runs the vmap simulation; a mesh routes through the shard_map
    engine (one device per shard, collectives only at partition cuts).
    requests defaults to one zero-params request per member plan. perms
    (from shard_perms(kg)) can be passed in to amortize the per-shard sort
    permutations across calls. dedup=True collapses identical (plan, params)
    requests to one executed instance. strict=True raises
    CapacityOverflowError on any request's overflow flag. backend selects
    the execution backend ("jnp" | "pallas" — bit-identical results).
    Returns the list of per-request (solutions, count, overflow).
    """
    check_gather_cap(gather_cap)
    if requests is None:
        requests = [(i, None) for i in range(len(bucket.plans))]
    exec_reqs, inverse = dedup_requests(requests, bucket.n_params) if dedup \
        else (requests, None)
    cache = cache if cache is not None else EngineCache()
    fn = cache.get(bucket.signature, join_impl=join_impl,
                   max_per_row=max_per_row, gather_cap=gather_cap, mesh=mesh,
                   backend=backend, kernel_blocks=kernel_blocks)
    pd, params = assemble_batch(bucket, exec_reqs)
    if perms is None:
        perms = shard_perms(kg)
    table, tmask, overflow = fetch_outputs(*fn(jnp.asarray(kg.triples),
                                               jnp.asarray(kg.valid),
                                               jnp.asarray(perms), pd, params))
    if inverse is None:
        out = extract_batch(bucket, exec_reqs, table, tmask, overflow)
    else:
        out = extract_fanout(bucket, exec_reqs, inverse, table, tmask,
                             overflow)
    if strict:
        for (_, _, ovf), (idx, _) in zip(out, requests):
            raise_on_overflow(ovf, bucket.plans[idx].query.name,
                              "sharded" if mesh is not None else "vmapped")
    return out


def run_sharded_batched(bucket: PlanBucket, kg: ShardedKG, mesh,
                        requests: list[tuple[int, np.ndarray | None]] | None
                        = None, **kw):
    """shard_map execution of a bucket batch on a real mesh axis: the named
    entry point the WorkloadServer routes through when given a mesh (mirrors
    federated.run_sharded for single plans)."""
    return run_batched(bucket, kg, requests, mesh=mesh, **kw)
