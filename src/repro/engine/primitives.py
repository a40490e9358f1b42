"""Shared scan/join primitives — the single reference implementation.

One home for the tensorized BGP building blocks that were previously
copy-pasted between the per-query engine (`engine/local.py`: `scan_shard`,
`join_step`) and the batched engine (`engine/batch.py`: `_scan_hit`,
`_join_data`): the fused triple-pattern predicate, the cumsum-based stable
compaction, the expand-join compatibility matrix, the merge-join
candidate ranges, and the rank search (searchsorted) under the last two.
Both engines call these, and the scan and compat-matrix Pallas kernels
(`kernels/kg_scan`, `kernels/kg_join`) use the jnp backend as their
differential reference; the candidate-range kernel is held to numpy.

The scan and join functions take ``backend`` ("jnp" | "pallas"): "jnp"
runs the dense XLA formulation below, "pallas" dispatches to the fused
kernels. The two backends are bit-identical on every value that is ever
read through a mask (hit masks, compaction index/selector triples,
candidate ranges), which is what makes the engine-level differential
guarantees possible.
"""
from __future__ import annotations

from collections import Counter
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

EQ_PAIRS = ((0, 1), (0, 2), (1, 2))
INT_MAX = np.int32(2**31 - 1)

BACKENDS = ("jnp", "pallas")


@dataclass(frozen=True)
class KernelBlocks:
    """Static tile sizes for the Pallas KG kernels — part of every engine
    cache key (a different tiling is a different compiled program).

    scan_rows: shard-block rows per kg_scan grid step;
    join_rows / join_cols: table-row / match-column tile of the kg_join
    kernels (candidate-range search and compat matrix). Defaults keep each
    tile's VMEM footprint small (< ~1 MiB) while keeping interpret-mode
    grids short on the shard/table sizes the test workloads produce.
    """
    scan_rows: int = 1024
    join_rows: int = 256
    join_cols: int = 512

    def __post_init__(self):
        for f in ("scan_rows", "join_rows", "join_cols"):
            v = getattr(self, f)
            if not isinstance(v, int) or isinstance(v, bool) or v < 8:
                raise ValueError(f"KernelBlocks.{f} must be an int >= 8, "
                                 f"got {v!r}")


DEFAULT_BLOCKS = KernelBlocks()


def check_backend(backend: str, kernel_blocks=None) -> KernelBlocks:
    """Validate a backend choice before any tracing happens; returns the
    resolved KernelBlocks (kernel_blocks is meaningless under jnp but
    harmless — it only keys compiled-engine caches)."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, "
                         f"got {backend!r}")
    if kernel_blocks is None:
        return DEFAULT_BLOCKS
    if not isinstance(kernel_blocks, KernelBlocks):
        raise ValueError(f"kernel_blocks must be a KernelBlocks or None, "
                         f"got {kernel_blocks!r}")
    return kernel_blocks


# ---------------------------------------------------------------------------
# triple-pattern scan
# ---------------------------------------------------------------------------

def eq_gates(eqs: tuple[tuple[int, int], ...]) -> np.ndarray:
    """Static intra-pattern equality pairs -> (3,) gate vector over EQ_PAIRS
    (the data-driven encoding the batched engine and the kernels use)."""
    g = np.zeros((3,), bool)
    for pair in eqs:
        g[EQ_PAIRS.index(tuple(sorted(pair)))] = True
    return g


def pattern_hit(cols, valid, spo, eq=None):
    """The triple-pattern predicate over the (s, p, o) columns `cols`.

    spo[j] is the constant of position j (-1 = wildcard, -2 =
    never-match) and eq[k] the gate of EQ_PAIRS[k] (or eq=None); every
    operand broadcasts against the columns, so the same code runs on
    (N,) columns with scalar constants here and on (rows, 128) tiles with
    lane-broadcast constants inside the Pallas kg_scan kernel.
    """
    hit = valid
    for c, v in zip(cols, spo):
        hit = hit & ((v == -1) | (c == v))
    hit = hit & (spo[0] != -2) & (spo[1] != -2) & (spo[2] != -2)
    if eq is not None:
        for k, (a, b) in enumerate(EQ_PAIRS):
            hit = hit & (~eq[k] | (cols[a] == cols[b]))
    return hit


def scan_predicate(triples, valid, spo, eq=None):
    """Fused triple-pattern hit mask over one shard block.

    triples: (N, 3) int32, valid: (N,) bool; spo: (3,) int32 with -1 =
    wildcard, -2 = never-match; eq: (3,) bool gates over EQ_PAIRS or None.
    Both backends evaluate this predicate (`pattern_hit`).
    """
    return pattern_hit([triples[:, j] for j in range(3)], valid,
                       [spo[j] for j in range(3)],
                       None if eq is None else [eq[k] for k in range(3)])


def scan_hits(triples, valid, spo, eq=None, *, backend: str = "jnp",
              blocks: KernelBlocks = DEFAULT_BLOCKS, interpret=None):
    """(hit, cum): the fused pattern predicate plus the inclusive hit-count
    prefix sum that the stable compaction consumes. Under "pallas" the
    predicate and the prefix sum run fused in one kg_scan kernel over
    shard blocks; cum is int32 either way so both backends are
    bit-identical."""
    if backend == "pallas":
        from repro.kernels.kg_scan.ops import scan_hits as pallas_scan
        return pallas_scan(triples, valid, spo,
                           eq if eq is not None
                           else jnp.zeros((3,), bool),
                           block_rows=blocks.scan_rows, interpret=interpret)
    hit = scan_predicate(triples, valid, spo, eq)
    return hit, jnp.cumsum(hit.astype(jnp.int32))


# ---------------------------------------------------------------------------
# rank search (searchsorted)
# ---------------------------------------------------------------------------

#: On an accelerator a rank search into sorted blocks of at most
#: RANK_COMPARE_MAX_KEYS keys counts, for every query, the keys below it
#: (`compare_all`: no gather, one fused reduce); past it, and on the CPU
#: at every size, it binary-searches (`scan`). On a TPU v5e each
#: binary-search level is a round of dependent gathers (about 10 ns a
#: query) and a compare about 1.1 ps a key; XLA:CPU gathers cheaply
#: (PERF.md, §6; `benchmarks/bench_rank.py` re-measures the crossover).
RANK_COMPARE_MAX_KEYS = 131072

_rank_log: ContextVar[Counter | None] = ContextVar("rank_log", default=None)


def rank_method(n_keys: int, platform: str) -> str:
    """The jnp.searchsorted method for a sorted block of `n_keys` keys on
    `platform` ("cpu", "tpu", ...), from these static facts alone."""
    if platform != "cpu" and n_keys <= RANK_COMPARE_MAX_KEYS:
        return "compare_all"
    return "scan"


def _ranks(blocks, queries, sides, method: str):
    """jnp.searchsorted of `queries` into each sorted row of `blocks` (B,
    C), one (B, Q) array per side. "scan" binary-searches: ceil(log2(C +
    1)) dependent rounds of one gathered key per query. "compare_all"
    counts the keys below each query: C compares a query, no gather."""
    return tuple(jax.vmap(lambda k, s=s: jnp.searchsorted(
        k, queries, side=s, method=method))(blocks) for s in sides)


@contextmanager
def rank_sites():
    """Count the rank searches traced inside the block by the method they
    run on the default backend."""
    log = Counter()
    token = _rank_log.set(log)
    try:
        yield log
    finally:
        _rank_log.reset(token)


def rank_sorted(keys, queries, *sides):
    """jnp.searchsorted(keys, queries, side=s) for each side in `sides`,
    integer for integer, by the method `rank_method` picks for the
    platform the program is lowered for.

    keys: (C,) sorted, or (B, C) with each block sorted; queries: (Q,), in
    any order. Returns one int32 array per side, keys.shape[:-1] + (Q,)."""
    blocks = keys if keys.ndim == 2 else keys[None]
    n_keys = blocks.shape[1]
    log = _rank_log.get()
    if log is not None:
        log[rank_method(n_keys, jax.default_backend())] += 1

    def on(platform):
        method = rank_method(n_keys, platform)

        def ranks(blocks, queries):
            with jax.named_scope(f"rank_{method}"):
                return _ranks(blocks, queries, sides, method)
        return ranks

    ranks = jax.lax.platform_dependent(blocks, queries, cpu=on("cpu"),
                                       default=on("tpu"))
    return ranks if keys.ndim == 2 else tuple(r[0] for r in ranks)


# ---------------------------------------------------------------------------
# stable compaction
# ---------------------------------------------------------------------------

def select_from_cum(cum, cap: int):
    """Stable compaction from an inclusive prefix sum: (idx, sel, total)
    where idx[j] is the position of the j-th set entry (clamped past
    `total`), sel = arange < total. The cumsum may come from jnp or from
    the fused kg_scan kernel — the searchsorted selection is identical."""
    n = cum.shape[0]
    k = min(cap, n)
    total = cum[-1]
    idx, = rank_sorted(cum, jnp.arange(1, k + 1, dtype=cum.dtype), "left")
    idx = jnp.clip(idx, 0, n - 1)
    sel = jnp.arange(k) < total
    return idx, sel, total


def select_cap(mask, cap: int):
    """Stable compaction: (idx, sel, total) for the first `cap` set entries
    of mask. Built from a cumsum plus a vectorized rank search — XLA:CPU
    runs sort, top_k, and vmapped scatter at ~100-200ns/element, an order
    of magnitude slower than elementwise + gather ops, and this compaction
    runs once per plan step per (batch, shard) instance."""
    return select_from_cum(jnp.cumsum(mask.astype(jnp.int32)), cap)


def compact(matches: jax.Array, mask: jax.Array, cap: int):
    """Keep the first `cap` valid rows (post-gather compaction). Returns
    (matches', mask', overflow); rows past the valid prefix are clamped
    repeats of the last row, dead under mask'."""
    idx, sel, total = select_cap(mask, cap)
    m = matches[idx]
    if m.shape[0] < cap:            # source smaller than the capacity: pad
        pad = cap - m.shape[0]
        m = jnp.pad(m, ((0, pad),) + ((0, 0),) * (m.ndim - 1),
                    constant_values=-1)
        sel = jnp.pad(sel, (0, pad))
    return m, sel, total > cap


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

def static_kind_col(shared, new, n_vars: int):
    """((3,) kind, (3,) col) int32 arrays from a plan step's static
    shared/new tuples — the data-driven encoding (kind 0 = unused,
    1 = shared/join var, 2 = new var) shared with PlanData."""
    kind = np.zeros((3,), np.int32)
    col = np.zeros((3,), np.int32)
    for pos, c_ in shared:
        kind[pos], col[pos] = 1, min(c_, max(0, n_vars - 1))
    for pos, c_ in new:
        kind[pos], col[pos] = 2, min(c_, max(0, n_vars - 1))
    return kind, col


def compat_matrix(table, tmask, matches, mmask, kind, col, *,
                  backend: str = "jnp",
                  blocks: KernelBlocks = DEFAULT_BLOCKS, interpret=None):
    """(R, C) bool expand-join compatibility matrix: row r joins match c iff
    both are live and every shared position's match value equals the row's
    bound variable. kind/col: (3,) int32 as in static_kind_col/PlanData.
    The "pallas" backend computes the same matrix tiled in VMEM
    (kernels/kg_join), fusing the per-position predicates with the
    mask outer product."""
    if backend == "pallas":
        from repro.kernels.kg_join.ops import compat_matrix as pallas_compat
        return pallas_compat(table, tmask, matches, mmask, kind, col,
                             block_rows=blocks.join_rows,
                             block_cols=blocks.join_cols, interpret=interpret)
    V = table.shape[1]
    compat = tmask[:, None] & mmask[None, :]
    for pos in range(3):
        cc = jnp.clip(col[pos], 0, V - 1)
        compat = compat & jnp.where(
            kind[pos] == 1,
            jnp.take(table, cc, axis=1)[:, None] == matches[None, :, pos],
            True)
    return compat


def join_ranges(keys, rkey, *, backend: str = "jnp",
                blocks: KernelBlocks = DEFAULT_BLOCKS, interpret=None):
    """Merge-join candidate ranges: for sorted keys (per block) and table
    row keys rkey, return (lo, hi) with lo[.., r] = #{keys < rkey[r]} and
    hi[.., r] = #{keys <= rkey[r]} — exactly jnp.searchsorted left/right
    on a sorted array (`rank_sorted` on the jnp backend). keys: (C,) or
    (S_b, C) int32 (invalid entries INT_MAX-padded, which keeps them
    sorted); rkey: (R,) int32 < INT_MAX. The "pallas" backend computes
    the counting formulation blocked over (row, column) tiles — no binary
    search, no gathers — which is integer-identical to searchsorted."""
    if backend == "pallas":
        from repro.kernels.kg_join.ops import join_ranges as pallas_ranges
        return pallas_ranges(keys, rkey, block_rows=blocks.join_rows,
                             block_cols=blocks.join_cols, interpret=interpret)
    return rank_sorted(keys, rkey, "left", "right")
