"""Fused masked triple-pattern scan Pallas kernel.

One grid step per shard-row block: the SPO equality predicate (constants,
wildcards, never-match sentinels, intra-pattern equality gates) and the
block's inclusive hit-count prefix sum run fused in VMEM, so the hit mask
never round-trips to HBM between the predicate and the compaction that
consumes its cumsum. The public op stitches blocks together with one
elementwise add (see ops.py) — no cross-block carry lives in the kernel,
which keeps the grid embarrassingly parallel and the kernel safe under
jax.vmap batching (the batch axis becomes an extra grid dimension).

Layout: the shard block arrives column-major and lane-dense, as a
(4, rows, 128) int32 array — the s, p, o columns and the validity mask,
row r holding triples [128 r, 128 r + 128). The pattern arrives as an
(8, 128) int32 tile whose row k repeats one value across the lanes (s, p,
o constants, then the three equality gates), so every operand is a whole
(8, 128) int32 tile and the pattern stays a per-request VMEM operand that
vmap can batch (scalar-prefetch operands cannot be batched without a
loop). Outputs are (rows, 128) int32 tiles.

The in-block prefix sum is a log-step shift-add scan in row-major order:
lane rotations within each 128-wide row, then the same over the row
totals along sublanes. int32 adds are associative, so the result is
bit-identical to jnp.cumsum on the reference path.

VMEM per step: block_rows * (4 + 2) int32 — 24 KiB at the default
1024-row block, far under the ~16 MiB budget.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.engine.primitives import pattern_hit

LANES = 128
TILE_ROWS = 8 * LANES      # triples per (8, 128) int32 tile


def _shift_scan(x, axis: int):
    """Inclusive prefix sum of x along `axis` by log-step rotate-and-add
    (rotated-in wraparound entries are masked to zero)."""
    n = x.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    d = 1
    while d < n:
        x = x + jnp.where(pos >= d, pltpu.roll(x, d, axis), 0)
        d *= 2
    return x


def _scan_kernel(pat_ref, cols_ref, hit_ref, incum_ref):
    # the predicate is THE shared reference implementation, inlined per
    # block (pure elementwise jnp — traces identically inside the kernel),
    # so engine backend and kernel cannot drift apart
    hit = pattern_hit([cols_ref[j] for j in range(3)], cols_ref[3] != 0,
                      [pat_ref[j:j + 1, :] for j in range(3)],
                      [pat_ref[3 + k:4 + k, :] != 0 for k in range(3)])
    x = jnp.where(hit, 1, 0)
    hit_ref[...] = x
    row_tot = jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True), x.shape)
    # in-row inclusive scan + the exclusive scan of the rows before it
    incum_ref[...] = _shift_scan(x, 1) + _shift_scan(row_tot, 0) - row_tot


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def scan_hits_kernel(pattern: jax.Array, cols: jax.Array, *,
                     block_rows: int = TILE_ROWS, interpret: bool = False):
    """(hit, incum): (rows, 128) int32 each, the hit flag and the in-block
    inclusive hit count of every triple of `cols` (4, rows, 128) under the
    (8, 128) `pattern` tile; block_rows % TILE_ROWS == 0 and the triple
    count is a block multiple (pad first; see ops.scan_hits)."""
    rows = cols.shape[1]
    br = block_rows // LANES
    assert block_rows % TILE_ROWS == 0 and rows % br == 0, \
        (cols.shape, block_rows)
    tile = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _scan_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((8, LANES), lambda i: (0, 0)),          # pattern
            pl.BlockSpec((4, br, LANES), lambda i: (0, i, 0)),   # s,p,o,valid
        ],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32)] * 2,
        interpret=interpret,
        name="kg_scan",
    )(pattern, cols)
