"""Differential references for the blocked merge-join kernels.

The candidate ranges are checked against numpy's searchsorted, not the
engine's jnp rank search, so the kernel and the jnp backend are each held
to an independent oracle. The compat matrix's oracle is the engine's jnp
backend (`engine/primitives.compat_matrix`).
"""
from __future__ import annotations

import numpy as np

from repro.engine.primitives import compat_matrix


def join_ranges_ref(keys, rkey):
    """(lo, hi) candidate ranges: searchsorted left/right of each table-row
    key into the (per-block) sorted match keys. keys: (C,) or (S_b, C)
    int32 with INT_MAX invalid padding; rkey: (R,) int32 < INT_MAX."""
    keys, rkey = np.asarray(keys), np.asarray(rkey)
    blocks = keys if keys.ndim == 2 else keys[None]
    lo, hi = (np.stack([np.searchsorted(k, rkey, side=s) for k in blocks]
                       ).astype(np.int32) for s in ("left", "right"))
    return (lo, hi) if keys.ndim == 2 else (lo[0], hi[0])


def compat_matrix_ref(table, tmask, matches, mmask, kind, col):
    """(R, C) bool expand-join compatibility matrix (see primitives)."""
    return compat_matrix(table, tmask, matches, mmask, kind, col,
                         backend="jnp")
