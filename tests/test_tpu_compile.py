"""Compile rehearsal for one TPU v5e chip, with no chip attached.

The KG kernels (interpret mode off) and one bucket engine per backend are
lowered and compiled for a described ``v5e:2x2`` topology at the shapes of
the ``chip_smoke.py`` deployment: LUBM at scale 1.0 (one university) on 3
WawPart shards, vmapped on one chip, batch 64. The TPU compiler refuses
here what it would refuse on the chip (block shapes Mosaic cannot tile,
programs over the chip's memory), so these guard every change at no chip
time. Nothing runs, so nothing here says anything about results or speed.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9          # one v5e chip (Google Cloud, "TPU v5e")
SCALE, N_SHARDS, BATCH = 1.0, 3, 64   # the chip_smoke.py deployment


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture
def native_kernels(monkeypatch):
    """Kernels as the chip runs them: the ops pick interpret mode from the
    default backend, which is the CPU here."""
    from repro.kernels.kg_join import ops as join_ops
    from repro.kernels.kg_scan import ops as scan_ops
    for mod in (scan_ops, join_ops):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)


@pytest.fixture(scope="module")
def smoke_server():
    from repro.launch.serve import (WorkloadServer, build_dataset,
                                    build_partition)
    store, queries = build_dataset("lubm", SCALE)
    part = build_partition("wawpart", store, queries, N_SHARDS)
    return WorkloadServer(queries, part)


def _compile(fn, *args):
    """Compile for the described chip; check it fits and holds a kernel
    iff it should. Returns the compiled text."""
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    return compiled.as_text()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_kg_scan_compiles(one_chip, smoke_server):
    from repro.kernels.kg_scan.ops import scan_hits
    n = smoke_server.kg.cap
    text = _compile(
        lambda t, v, s, e: scan_hits(t, v, s, e, interpret=False),
        _shape(one_chip, (n, 3), jnp.int32),
        _shape(one_chip, (n,), jnp.bool_),
        _shape(one_chip, (3,), jnp.int32), _shape(one_chip, (3,), jnp.bool_))
    assert "tpu_custom_call" in text


def test_kg_join_ranges_compiles(one_chip, smoke_server):
    from repro.kernels.kg_join.ops import join_ranges
    sig = smoke_server.buckets[-1].signature       # the widest bucket
    text = _compile(lambda k, r: join_ranges(k, r, interpret=False),
                    _shape(one_chip, (N_SHARDS, max(sig.scan_caps)),
                           jnp.int32),
                    _shape(one_chip, (sig.table_cap,), jnp.int32))
    assert "tpu_custom_call" in text


def test_kg_compat_compiles(one_chip, smoke_server):
    from repro.kernels.kg_join.ops import compat_matrix
    sig = smoke_server.buckets[1].signature
    r, v, c = sig.table_cap, sig.n_vars, max(sig.scan_caps)
    text = _compile(
        lambda t, tm, m, mm, k, col: compat_matrix(t, tm, m, mm, k, col,
                                                   interpret=False),
        _shape(one_chip, (r, v), jnp.int32), _shape(one_chip, (r,), jnp.bool_),
        _shape(one_chip, (c, 3), jnp.int32), _shape(one_chip, (c,), jnp.bool_),
        _shape(one_chip, (3,), jnp.int32), _shape(one_chip, (3,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bucket_engine_compiles(one_chip, smoke_server, native_kernels,
                                backend):
    """The cut bucket (LUBM Q1/Q3/Q5/Q10/Q13: one step gathers across
    shards) at batch 64, through the server's own engine cache."""
    from repro.engine.batch import EngineCache, PlanData
    bucket = smoke_server.buckets[1]
    sig = bucket.signature
    fn = EngineCache().get(sig, join_impl=smoke_server.join_impl,
                           backend=backend)
    kg, B, L = smoke_server.kg, BATCH, sig.n_steps
    i32 = jnp.int32
    pd = PlanData(*(_shape(one_chip, s, d) for s, d in (
        ((B, L, 3), i32), ((B, L, 3), i32), ((B, L, 3), jnp.bool_),
        ((B, L, 3), i32), ((B, L, 3), i32), ((B, L, kg.n_shards), jnp.bool_),
        ((B, L), jnp.bool_))))
    compiled = fn.lower(
        _shape(one_chip, kg.triples.shape, i32),
        _shape(one_chip, kg.valid.shape, jnp.bool_),
        _shape(one_chip, (kg.n_shards, 3, kg.cap), i32), pd,
        _shape(one_chip, (B, bucket.n_params), i32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_rank_search_compiles_gather_free(one_chip, smoke_server):
    """The widest bucket's gathered merge steps (3 and 4): each of 3
    vmapped shards ranks its table's row keys in 3 sorted match blocks.
    The shape rule counts there, so no binary-search `while` loop is left."""
    from repro.engine.primitives import rank_method, rank_sorted
    sig = smoke_server.buckets[-1].signature       # the widest bucket
    C, R = sig.scan_caps[3], sig.table_cap
    assert rank_method(C, "tpu") == "compare_all"
    text = _compile(
        jax.vmap(lambda k, r: rank_sorted(k, r, "left", "right")),
        _shape(one_chip, (N_SHARDS, N_SHARDS, C), jnp.int32),
        _shape(one_chip, (N_SHARDS, R), jnp.int32))
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert not [ln for ln in loops if "searchsorted" in ln]
    assert "rank_compare_all" in text
