"""Blocked merge-join Pallas kernels over per-shard sorted match blocks.

Two kernels back the engine's join variants:

* ``join_ranges_kernel`` — the merge side of the sort-free merge join: for
  every binding-table row key, locate its candidate range [lo, hi) in each
  shard block's sorted match keys (the per-shard sort perms materialized by
  ``engine/batch.shard_perms`` make the keys sorted by construction). A
  binary search is gather-heavy and serializes on TPU; instead the kernel
  counts — lo[r] = #{keys < rkey[r]}, hi[r] = #{keys <= rkey[r]} — which on
  a sorted array is integer-identical to ``jnp.searchsorted`` left/right.
  The count accumulates tile by tile over the match-column grid axis in a
  VMEM scratch register, so the kernel is pure VPU compare+reduce work with
  no gathers and no data-dependent control flow. Seed, expansion, and
  semijoin steps all consume these ranges: expansion and semijoin share the
  (row, candidate) windows directly, and the seed step is the degenerate
  0-column case the engine routes through the fused kg_scan compaction.

* ``compat_matrix_kernel`` — the expand-and-filter (paper-faithful) join's
  R x C compatibility matrix, tiled: the live-row x live-match outer
  product fused with up to three shared-position equality predicates whose
  columns are picked at run time (kind/col are data, one engine serves
  every plan in a bucket).

Layout: every operand is lane-dense int32 in whole (8, 128) tiles. Row
keys lie along lanes and the match keys of each block are turned onto
sublanes in VMEM (one transpose per tile), so both (lo, hi) come out as
lane-dense rows. The compat kernel reads the binding table padded to 128
lanes with the row mask in lane 127, the matches column-major with the
match mask as a fourth row, and kind/col as an (8, 128) tile whose row k
repeats one value across the lanes — a per-request VMEM operand that vmap
batches as an extra grid axis; its bound-column select is a one-hot lane
reduction. Masks travel as int32 and the compat matrix comes back as int8
(Mosaic keeps no i1 arrays in memory).

VMEM per step at the default tiles: the (512, 256) int32 compare tile of
the range search and the (256, 512) compat tile plus operands — about
1 MiB, leaving the double-buffer headroom the guide budget asks for.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
MASK_LANE = LANES - 1      # compat table lane that carries the row mask


def _ranges_kernel(keys_ref, rkey_ref, lo_ref, hi_ref, acc_lo, acc_hi, *,
                   n_blocks: int, n_cblocks: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_lo[...] = jnp.zeros_like(acc_lo)
        acc_hi[...] = jnp.zeros_like(acc_hi)

    keys = keys_ref[...].T                # (bc, S8): match keys on sublanes
    rk = rkey_ref[...]                    # (1, br): row keys on lanes
    for s in range(n_blocks):
        col = keys[:, s:s + 1]            # (bc, 1)
        acc_lo[s:s + 1, :] += jnp.sum(jnp.where(col < rk, 1, 0), axis=0,
                                      keepdims=True)
        acc_hi[s:s + 1, :] += jnp.sum(jnp.where(col <= rk, 1, 0), axis=0,
                                      keepdims=True)

    @pl.when(k == n_cblocks - 1)
    def _():
        lo_ref[...] = acc_lo[...]
        hi_ref[...] = acc_hi[...]


@partial(jax.jit, static_argnames=("n_blocks", "block_rows", "block_cols",
                                   "interpret"))
def join_ranges_kernel(keys: jax.Array, rkey: jax.Array, *, n_blocks: int,
                       block_rows: int = 256, block_cols: int = 512,
                       interpret: bool = False):
    """keys: (S8, C) int32 sorted per row (INT_MAX invalid padding; the
    first n_blocks rows are real, S8 % 8 == 0), rkey: (1, R) int32 <
    INT_MAX; C % block_cols == 0, R % block_rows == 0, both blocks lane
    multiples (pad first; see ops.join_ranges). Returns (lo, hi): (S8, R)
    int32, rows past n_blocks zero."""
    s8, c = keys.shape
    r = rkey.shape[1]
    assert s8 % SUBLANES == 0 and n_blocks <= s8, (keys.shape, n_blocks)
    assert c % block_cols == 0 and r % block_rows == 0, \
        (keys.shape, rkey.shape, block_rows, block_cols)
    assert block_rows % LANES == 0 and block_cols % LANES == 0, \
        (block_rows, block_cols)
    nc = c // block_cols
    out = pl.BlockSpec((s8, block_rows), lambda i, k: (0, i))
    return pl.pallas_call(
        partial(_ranges_kernel, n_blocks=n_blocks, n_cblocks=nc),
        grid=(r // block_rows, nc),
        in_specs=[
            pl.BlockSpec((s8, block_cols), lambda i, k: (0, k)),
            pl.BlockSpec((1, block_rows), lambda i, k: (0, i)),
        ],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((s8, r), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((s8, block_rows), jnp.int32),
                        pltpu.VMEM((s8, block_rows), jnp.int32)],
        interpret=interpret,
        name="kg_join_ranges",
    )(keys, rkey)


def _compat_kernel(jn_ref, table_ref, matches_ref, out_ref):
    tb = table_ref[...]                   # (br, 128): V columns + row mask
    mt = matches_ref[...]                 # (4, bc): s, p, o rows + mask row
    lane = jax.lax.broadcasted_iota(jnp.int32, tb.shape, 1)

    def column(sel, src):                 # (br, 1): src[:, sel]
        return jnp.sum(jnp.where(lane == sel, src, 0), axis=1, keepdims=True)

    compat = (column(MASK_LANE, tb) != 0) & (mt[3:4, :] != 0)
    for pos in range(3):
        kind = jn_ref[pos:pos + 1, :]                 # lane-broadcast rows
        col = jn_ref[3 + pos:4 + pos, :]
        join = column(0, jnp.broadcast_to(kind, tb.shape)) == 1
        compat = compat & (~join | (column(col, tb) == mt[pos:pos + 1, :]))
    out_ref[...] = jnp.where(compat, 1, 0).astype(jnp.int8)


@partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def compat_matrix_kernel(join: jax.Array, table: jax.Array,
                         matches: jax.Array, *, block_rows: int = 256,
                         block_cols: int = 512, interpret: bool = False):
    """(R, C) int8 compat matrix. join: (8, 128) kind/col tile; table:
    (R, 128) int32 with the row mask in lane 127; matches: (4, C) int32
    with the match mask as row 3. R % block_rows == 0 (a multiple of 32),
    C % block_cols == 0 (a lane multiple) — pad first; see
    ops.compat_matrix."""
    r = table.shape[0]
    c = matches.shape[1]
    assert r % block_rows == 0 and c % block_cols == 0, \
        (table.shape, matches.shape, block_rows, block_cols)
    return pl.pallas_call(
        _compat_kernel,
        grid=(r // block_rows, c // block_cols),
        in_specs=[
            pl.BlockSpec((SUBLANES, LANES), lambda i, j: (0, 0)),  # kind/col
            pl.BlockSpec((block_rows, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((4, block_cols), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int8),
        interpret=interpret,
        name="kg_join_compat",
    )(join, table, matches)
