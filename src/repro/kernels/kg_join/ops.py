"""Public merge-join ops: layout, padding + dispatch for the kg_join kernels."""
from __future__ import annotations

import jax.numpy as jnp

from repro.engine.primitives import INT_MAX as _INT_MAX
from repro.kernels import default_interpret, round_up
from repro.kernels.kg_join.kernel import (LANES, MASK_LANE, SUBLANES,
                                          compat_matrix_kernel,
                                          join_ranges_kernel)
from repro.kernels.kg_join.ref import compat_matrix_ref, join_ranges_ref


def _pad_to(n: int, block: int, align: int) -> tuple[int, int]:
    """(padded size, effective block): the block rounds up to `align` and
    shrinks to the (aligned) array when the array is smaller, so short
    operands run as a single tile."""
    b = min(round_up(block, align), round_up(max(1, n), align))
    return round_up(max(1, n), b), b


def join_ranges(keys, rkey, *, block_rows: int = 256, block_cols: int = 512,
                interpret: bool | None = None):
    """Candidate ranges (lo, hi) of each table-row key in the sorted match
    keys — integer-identical to jnp.searchsorted left/right.

    keys: (C,) or (S_b, C) int32, sorted per row with INT_MAX invalid
    padding; rkey: (R,) int32, values < INT_MAX (term ids and the -1
    unbound sentinel both qualify). Key padding (extra columns, and extra
    blocks up to a sublane multiple) reuses INT_MAX, which keeps rows
    sorted and never counts; row padding is sliced off.
    """
    keys = jnp.asarray(keys)
    squeeze = keys.ndim == 1
    if squeeze:
        keys = keys[None]
    sb, c = keys.shape
    r = rkey.shape[0]
    cp, bc = _pad_to(c, block_cols, LANES)
    rp, br = _pad_to(r, block_rows, LANES)
    keys = jnp.pad(keys, ((0, round_up(sb, SUBLANES) - sb), (0, cp - c)),
                   constant_values=_INT_MAX)
    rkey = jnp.pad(jnp.asarray(rkey, jnp.int32), (0, rp - r))[None]
    interp = default_interpret() if interpret is None else interpret
    lo, hi = join_ranges_kernel(keys, rkey, n_blocks=sb, block_rows=br,
                                block_cols=bc, interpret=interp)
    lo, hi = lo[:sb, :r], hi[:sb, :r]
    return (lo[0], hi[0]) if squeeze else (lo, hi)


def join_ranges_reference(keys, rkey):
    return join_ranges_ref(keys, rkey)


def compat_matrix(table, tmask, matches, mmask, kind, col, *,
                  block_rows: int = 256, block_cols: int = 512,
                  interpret: bool | None = None):
    """(R, C) bool expand-join compatibility matrix, tiled in VMEM.

    Row/column padding enters with masks off, so padded slots are
    incompatible by construction and the slice-back is exact.
    """
    r, v = table.shape
    c = matches.shape[0]
    assert v < MASK_LANE, f"binding table too wide for one lane tile: {v}"
    rp, br = _pad_to(r, block_rows, 4 * SUBLANES)   # int8 output tiles
    cp, bc = _pad_to(c, block_cols, LANES)
    tab = jnp.zeros((rp, LANES), jnp.int32)
    tab = tab.at[:r, :v].set(jnp.asarray(table, jnp.int32))
    tab = tab.at[:r, MASK_LANE].set(jnp.asarray(tmask, jnp.int32))
    mt = jnp.concatenate([jnp.asarray(matches, jnp.int32).T,
                          jnp.asarray(mmask, jnp.int32)[None]])
    mt = jnp.pad(mt, ((0, 0), (0, cp - c)))
    join = jnp.concatenate([jnp.asarray(kind, jnp.int32),
                            jnp.clip(jnp.asarray(col, jnp.int32), 0, v - 1),
                            jnp.zeros((2,), jnp.int32)])
    join = jnp.broadcast_to(join[:, None], (SUBLANES, LANES))
    interp = default_interpret() if interpret is None else interpret
    out = compat_matrix_kernel(join, tab, mt, block_rows=br, block_cols=bc,
                               interpret=interp)
    return out[:r, :c] != 0


def compat_matrix_reference(table, tmask, matches, mmask, kind, col):
    return compat_matrix_ref(jnp.asarray(table), jnp.asarray(tmask),
                             jnp.asarray(matches), jnp.asarray(mmask),
                             jnp.asarray(kind), jnp.asarray(col))
