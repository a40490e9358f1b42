"""Public fused triple-scan op: layout, padding, block stitching, dispatch."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import default_interpret, round_up
from repro.kernels.kg_scan.kernel import LANES, TILE_ROWS, scan_hits_kernel
from repro.kernels.kg_scan.ref import scan_hits_ref


def scan_hits(triples, valid, spo, eq, *, block_rows: int = 1024,
              interpret: bool | None = None):
    """(hit (N,) bool, cum (N,) int32): fused triple-pattern predicate plus
    inclusive hit-count prefix sum over a padded shard block.

    The block is laid out column-major in whole (8, 128) int32 tiles
    (block_rows rounds up to a multiple of 1024 triples, and shrinks to
    the padded block when that is smaller); padded rows are invalid and
    can never hit. Per-block partial sums from the kernel are stitched
    into the global cumsum with one exclusive-scan-plus-add — int32 adds
    all the way, so the result is bit-identical to the jnp reference
    (kg_scan.ref.scan_hits_ref / the engine's jnp backend).
    """
    n = triples.shape[0]
    bn = min(round_up(block_rows, TILE_ROWS), round_up(n, TILE_ROWS))
    n_pad = round_up(n, bn)
    cols = jnp.concatenate([jnp.asarray(triples, jnp.int32).T,
                            jnp.asarray(valid, jnp.int32)[None]])
    cols = jnp.pad(cols, ((0, 0), (0, n_pad - n))).reshape(4, -1, LANES)
    pattern = jnp.concatenate([jnp.asarray(spo, jnp.int32),
                               jnp.asarray(eq, jnp.int32),
                               jnp.zeros((2,), jnp.int32)])
    pattern = jnp.broadcast_to(pattern[:, None], (8, LANES))
    interp = default_interpret() if interpret is None else interpret
    hit, incum = scan_hits_kernel(pattern, cols, block_rows=bn,
                                  interpret=interp)
    incum = incum.reshape(-1, bn)
    counts = incum[:, -1]
    offs = jnp.cumsum(counts) - counts              # exclusive block offsets
    cum = (incum + offs[:, None]).reshape(-1)
    return hit.reshape(-1)[:n] != 0, cum[:n]


def scan_hits_reference(triples, valid, spo, eq=None):
    return scan_hits_ref(triples, valid, jnp.asarray(spo, jnp.int32),
                         None if eq is None else jnp.asarray(eq, jnp.bool_))
