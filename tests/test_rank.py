"""The engine's rank search (`primitives.rank_sorted`) against numpy.

Every method is called directly and must return np.searchsorted's
integers; the compaction sites built on it (`select_from_cum`,
`_select_windows`, `_select_rows`) must return the (idx, sel, total) of
their binary-search definitions whichever method the rule picks; and
the rule itself must count at every LUBM bucket's shapes on the chip,
binary-search past its keys-per-block crossover, and binary-search on
the CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine import primitives
from repro.engine.batch import _select_rows, _select_windows
from repro.engine.primitives import (INT_MAX, RANK_COMPARE_MAX_KEYS,
                                     rank_method, rank_sites, rank_sorted,
                                     select_from_cum)

RNG = np.random.default_rng(14)
SIDES = [("left",), ("right",), ("left", "right"), ("right", "left")]
METHODS = ("scan", "compare_all")      # what `rank_method` can pick


def _blocks(case: str) -> tuple[np.ndarray, np.ndarray]:
    """(keys (B, C) sorted per block, queries (Q,)) for one named case."""
    if case == "dups_padded":          # repeats, INT_MAX-padded tails
        keys = np.sort(RNG.integers(-1, 12, (3, 40)), axis=1)
        keys[:, 25:] = INT_MAX
        return keys.astype(np.int32), RNG.integers(-2, 14, 64)
    if case == "all_invalid_block":    # one block holds no valid key
        keys = np.sort(RNG.integers(0, 30, (3, 16)), axis=1)
        keys[1] = INT_MAX
        return keys.astype(np.int32), RNG.integers(-1, 32, 50)
    if case == "outside":              # queries below and above every key
        keys = np.sort(RNG.integers(100, 200, (2, 33)), axis=1)
        q = np.concatenate([RNG.integers(-5, 100, 20),
                            RNG.integers(200, 400, 20), [100, 199, -1]])
        return keys.astype(np.int32), q
    if case == "one_key":
        return np.array([[7]], np.int32), np.array([6, 7, 8, 7])
    raise ValueError(case)


CASES = ["dups_padded", "all_invalid_block", "outside", "one_key"]


def _want(keys, q, side):
    return np.stack([np.searchsorted(k, q, side=side) for k in keys])


@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("sides", SIDES, ids="-".join)
@pytest.mark.parametrize("case", CASES)
def test_rank_method_matches_numpy(method, sides, case):
    keys, q = _blocks(case)
    q = q.astype(np.int32)
    got = primitives._ranks(jnp.asarray(keys), jnp.asarray(q), sides,
                            method)
    assert len(got) == len(sides)
    for r, s in zip(got, sides):
        assert r.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(r), _want(keys, q, s))


@pytest.fixture(params=METHODS)
def forced(request, monkeypatch):
    """Make the rule pick one method for every size and platform."""
    monkeypatch.setattr(primitives, "rank_method",
                        lambda n_keys, platform: request.param)
    return request.param


@pytest.mark.parametrize("ndim", [1, 2])
def test_rank_sorted_shapes_and_values(forced, ndim):
    """The public helper, 1-D keys and (S_b, C) blocks, either method."""
    keys, q = _blocks("dups_padded")
    q = q.astype(np.int32)
    k = keys if ndim == 2 else keys[0]
    with rank_sites() as sites:
        lo, hi = rank_sorted(jnp.asarray(k), jnp.asarray(q), "left", "right")
    assert dict(sites) == {forced: 1}
    assert lo.shape == hi.shape == k.shape[:-1] + q.shape
    want = keys if ndim == 2 else keys[:1]
    np.testing.assert_array_equal(np.asarray(lo).reshape(-1, q.size),
                                  _want(want, q, "left"))
    np.testing.assert_array_equal(np.asarray(hi).reshape(-1, q.size),
                                  _want(want, q, "right"))


# -- the compaction sites: the same (idx, sel, total) as the binary search --

def _select_from_cum_np(cum, cap):
    n = cum.shape[0]
    k = min(cap, n)
    idx = np.clip(np.searchsorted(cum, np.arange(1, k + 1), side="left"),
                  0, n - 1)
    return idx, np.arange(k) < cum[-1], cum[-1]


def _select_windows_np(n, width, cap):
    cum = np.cumsum(n)
    j = np.arange(cap)
    g = np.clip(np.searchsorted(cum, j, side="right"), 0, n.shape[0] - 1)
    sel = j < cum[-1]
    idx = np.where(sel, g * width + j - (cum[g] - n[g]),
                   n.shape[0] * width - 1)
    return idx, sel, cum[-1]


def _select_rows_np(mask, cap):
    W, R = mask.shape
    wcum = np.cumsum(mask, axis=0)
    n = wcum[-1]
    cum = np.cumsum(n)
    j = np.arange(min(cap, W * R))
    g = np.clip(np.searchsorted(cum, j, side="right"), 0, R - 1)
    t = j - (cum[g] - n[g])
    w = np.sum(wcum[:, g] <= t[None, :], axis=0)
    sel = j < cum[-1]
    return np.where(sel, g * W + w, W * R - 1), sel, cum[-1]


def _check(got, want):
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), b)


@pytest.mark.parametrize("density,cap", [(0.05, 64), (0.6, 64), (0.3, 500),
                                         (0.0, 32)])
def test_select_from_cum_unchanged(forced, density, cap):
    mask = RNG.uniform(size=300) < density
    cum = np.cumsum(mask).astype(np.int32)
    got = jax.jit(select_from_cum, static_argnums=1)(jnp.asarray(cum), cap)
    _check(got, _select_from_cum_np(cum, cap))


@pytest.mark.parametrize("density,cap", [(0.05, 64), (0.7, 64), (0.0, 16)])
def test_select_windows_unchanged(forced, density, cap):
    width = 4
    n = (RNG.integers(0, width + 1, 90)
         * (RNG.uniform(size=90) < density)).astype(np.int32)
    got = jax.jit(_select_windows, static_argnums=(1, 2))(
        jnp.asarray(n), width, cap)
    _check(got, _select_windows_np(n, width, cap))


@pytest.mark.parametrize("density,cap", [(0.02, 48), (0.5, 48), (0.0, 16)])
def test_select_rows_unchanged(forced, density, cap):
    mask = RNG.uniform(size=(6, 40)) < density
    got = jax.jit(_select_rows, static_argnums=1)(jnp.asarray(mask), cap)
    _check(got, _select_rows_np(mask, cap))


# -- the shape rule ---------------------------------------------------------

@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("n_keys,tpu_method", [
    (16384, "compare_all"),   # widest bucket, steps 2-4: join ranges
    (36800, "compare_all"),   # scan compactions, step 5 join ranges
    (98304, "compare_all"),   # widest bucket, step 3: window selection
    (2048, "compare_all"),    # Q1's bucket: join ranges and compactions
    (512, "compare_all"),     # Q11's bucket
    (4 * 36800, "scan"),      # blocks past the count's crossover
])
def test_rank_rule(n_keys, tpu_method, platform):
    """On the chip the rule counts at every LUBM(1) site and binary-
    searches past its crossover; on the CPU it always binary-searches."""
    want = tpu_method if platform == "tpu" else "scan"
    assert rank_method(n_keys, platform) == want


def test_rank_rule_bounds_are_its_crossovers():
    c = RANK_COMPARE_MAX_KEYS
    assert rank_method(c, "tpu") == "compare_all"
    assert rank_method(c + 1, "tpu") == "scan"
    assert rank_method(1, "cpu") == "scan"


def test_rank_sites_counts_and_scopes():
    """Each traced rank search counts once under the method it runs on
    the default backend, and the program lowered for a platform carries
    that platform's `rank_<method>` scope and no other."""
    keys = jax.ShapeDtypeStruct((3, 64), jnp.int32)
    wide = jax.ShapeDtypeStruct((3, RANK_COMPARE_MAX_KEYS + 1), jnp.int32)
    q = jax.ShapeDtypeStruct((8,), jnp.int32)

    def f(k, w, q):
        return rank_sorted(k, q, "left", "right"), rank_sorted(w, q, "left")

    with rank_sites() as sites:
        traced = jax.jit(f).trace(keys, wide, q)
    assert dict(sites) == {"scan": 2}             # the tests run on the CPU
    scopes = {}
    for platform in ("cpu", "tpu"):
        text = traced.lower(lowering_platforms=(platform,)).as_text(
            debug_info=True)
        scopes[platform] = {m for m in ("scan", "compare_all")
                            if f"rank_{m}" in text}
    assert scopes == {"cpu": {"scan"}, "tpu": {"scan", "compare_all"}}
