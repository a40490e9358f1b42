"""End-to-end behaviour of the paper's system: partition a knowledge graph by
workload, rewrite queries, execute federated — answers identical to a
centralized store, with strictly less cross-shard communication than the
random baseline (the paper's Fig. 5-8 claim at the semantics level)."""
from pathlib import Path

import numpy as np
import pytest

from repro.core.partitioner import (centralized_partition, random_partition,
                                    wawpart_partition)
from repro.core.rewriter import workload_plans
from repro.engine.federated import ShardedKG, run_vmapped
from repro.engine.oracle import evaluate_bgp
from repro.engine.planner import make_plan
from repro.kg.workloads import bsbm_queries, lubm_queries


def _gather_bytes(plans, kg):
    """Static cross-shard traffic a workload needs under a placement."""
    total = 0
    for plan in plans:
        for step in plan.steps:
            if step.gather:
                total += kg.n_shards * step.scan_cap * 3 * 4
    return total


def test_end_to_end_lubm(lubm_small):
    queries = lubm_queries()
    ww = wawpart_partition(lubm_small, queries, n_shards=3)
    rnd = random_partition(lubm_small, queries, n_shards=3, seed=0)
    cen = centralized_partition(lubm_small, queries)

    kg_ww, kg_rnd, kg_cen = (ShardedKG.build(p) for p in (ww, rnd, cen))
    ww_plans, rnd_plans = [], []
    for q in queries:
        oracle = evaluate_bgp(lubm_small, q)
        for part, kg, acc in ((ww, kg_ww, ww_plans), (rnd, kg_rnd, rnd_plans),
                              (cen, kg_cen, None)):
            plan = make_plan(q, part)
            rows, n, ovf = run_vmapped(plan, kg)
            assert not ovf, (q.name, part.method)
            assert np.array_equal(rows, oracle), (q.name, part.method)
            if acc is not None:
                acc.append(plan)

    # the paper's claim, statically: workload-aware placement moves fewer
    # bytes across shards than random-by-predicate
    assert _gather_bytes(ww_plans, kg_ww) < _gather_bytes(rnd_plans, kg_rnd)
    # and rewrites fewer queries into federated form
    n_fed_ww = sum(1 for p in workload_plans(queries, ww)
                   if not p.is_local)
    n_fed_rnd = sum(1 for p in workload_plans(queries, rnd)
                    if not p.is_local)
    assert n_fed_ww <= n_fed_rnd


def test_end_to_end_bsbm(bsbm_small):
    queries = bsbm_queries()
    ww = wawpart_partition(bsbm_small, queries, n_shards=3)
    kg = ShardedKG.build(ww)
    for q in queries:
        plan = make_plan(q, ww)
        rows, n, ovf = run_vmapped(plan, kg)
        assert not ovf and np.array_equal(rows, evaluate_bgp(bsbm_small, q))


def test_balance_matches_paper_band(lubm_small):
    """Paper §4.1: WawPart shards within -8%..+15% of the mean."""
    part = wawpart_partition(lubm_small, lubm_queries(), n_shards=3)
    dev = part.balance_report()["rel_dev"]
    assert min(dev) >= -0.16 and max(dev) <= 0.16


@pytest.mark.parametrize("env_dir", ["outside", None])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache sits at
    a fixed directory inside the checkout."""
    import jax

    from repro.launch.serve import use_compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
