"""Fault-tolerant serving (repro.faults): chaos differentials, retry and
backoff mechanics, replica-degraded mode, transactional migration, LRU
engine-cache capping, graceful shutdown, and empty/edge drain paths."""
import numpy as np
import pytest

from repro.core.partitioner import Partitioning, wawpart_partition
from repro.faults import (DeadlineExceededError, FaultInjector, FaultPlan,
                          InjectedDispatchError, MigrationAbortedError,
                          RetryExhaustedError, RetryPolicy, ServingFault,
                          ShardDownError, ShutdownError, classify,
                          degraded_placement, uncovered_templates)
from repro.kg.workloads import lubm_queries
from repro.launch.serve import PipelineConfig, WorkloadServer


class FakeClock:
    """Deterministic injectable clock (same idiom as test_pipeline)."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _eq(a, b):
    """Result triples (solutions, count, overflow) compare exactly."""
    return (np.array_equal(a[0], b[0]) and a[1] == b[1] and a[2] == b[2])


@pytest.fixture(scope="module")
def lubm_served(lubm_tiny):
    queries = lubm_queries()
    part = wawpart_partition(lubm_tiny, queries, n_shards=3)
    return queries, part


def _stream(queries, n):
    return [(queries[i % len(queries)].name, None) for i in range(n)]


# ---- unit: plan parsing, classification, backoff -------------------------

def test_fault_plan_parse_grammar():
    plan = FaultPlan.parse("dispatch=0.25/3,down=1@0.5:2.0,"
                           "delay=0.1:0.2;0.4:0.5,abort=2,seed=7")
    assert plan.dispatch_fail_rate == 0.25
    assert plan.max_dispatch_failures == 3
    assert plan.shard_down == ((1, 0.5, 2.0),)
    assert plan.flush_delay == ((0.1, 0.2), (0.4, 0.5))
    assert plan.abort_migrations == 2
    assert plan.seed == 7
    assert not plan.empty
    assert FaultPlan.parse("").empty
    with pytest.raises(ValueError, match="unknown chaos key"):
        FaultPlan.parse("explode=1")
    with pytest.raises(ValueError, match="key=value"):
        FaultPlan.parse("dispatch")


def test_classify_transient_vs_permanent():
    from repro.engine.federated import CapacityOverflowError
    assert classify(CapacityOverflowError("full")) == "permanent"
    assert classify(ValueError("bad params")) == "permanent"
    assert classify(KeyError("no template")) == "permanent"
    assert classify(InjectedDispatchError("chaos")) == "transient"
    assert classify(ShardDownError("down")) == "transient"
    assert classify(RuntimeError("transport wobble")) == "transient"
    assert issubclass(InjectedDispatchError, ServingFault)


def test_backoff_deterministic_positive_and_capped():
    pol = RetryPolicy(base_ms=1.0, cap_ms=8.0, seed=3)
    prev = None
    for attempt in range(1, 8):
        b = pol.backoff_s(attempt, prev)
        assert b == pol.backoff_s(attempt, prev)   # deterministic
        assert 0 < b <= 8.0 / 1e3 + 1e-12
        assert b >= 1.0 / 1e3
        prev = b
    # a different seed decorrelates the schedule
    other = RetryPolicy(base_ms=1.0, cap_ms=8.0, seed=4)
    assert any(pol.backoff_s(k) != other.backoff_s(k) for k in range(1, 5))


def test_injector_noop_when_empty():
    inj = FaultInjector(FaultPlan())
    assert not inj.enabled
    inj.on_dispatch(0)                       # never raises
    assert inj.flush_delayed(0, 1.0) is False
    assert inj.shard_down_now(1.0) is None
    inj.check_migration_abort()
    assert inj.injected == {"dispatch": 0, "shard_down": 0,
                            "migration_abort": 0}


# ---- unit: degraded placement --------------------------------------------

def test_degraded_placement_rehomes_and_loses():
    class _Cat:
        sizes = {"a": 5, "b": 3, "c": 2}
    part = Partitioning(3, {"a": 0, "b": 0, "c": 1}, _Cat(),
                        np.array([8, 2, 0]), method="test",
                        replicas={"a": frozenset({2})})
    dpart, lost = degraded_placement(part, 0)
    assert dpart.unit_shard["a"] == 2          # re-homed to the live copy
    assert dpart.unit_shard["c"] == 1          # untouched
    assert lost == frozenset({"b"})            # only copy was on shard 0
    assert dpart.replicas == {}                # replicas dropped
    assert dpart.shard_sizes.tolist() == [3, 2, 5]  # lost b stays counted
    assert dpart.meta["degraded_shard"] == 0
    with pytest.raises(ValueError, match="not in 0..2"):
        degraded_placement(part, 9)


# ---- chaos differential: dispatch faults + retry --------------------------

def test_chaos_dispatch_retry_bit_identical(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 20)
    ref = WorkloadServer(queries, part,
                         pipeline=PipelineConfig(deadline_ms=None)
                         ).serve(reqs)

    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None),
                            faults=FaultPlan(seed=5, dispatch_fail_rate=0.5),
                            retry=RetryPolicy(max_attempts=8))
    got = server.serve(reqs)
    assert server.faults.injected["dispatch"] > 0, "schedule never fired"
    for a, b in zip(ref, got):
        assert b is not None and _eq(a, b)
    st = server.stats
    assert st["retries"] > 0 and st["shed"] == 0
    assert st["served"] == len(reqs)


def test_chaos_no_retry_sheds_typed(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 16)
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None),
                            faults=FaultPlan(seed=1, dispatch_fail_rate=1.0,
                                             max_dispatch_failures=2))
    tickets = [server.submit(n, p, _pump=False) for n, p in reqs]
    server.drain()                  # runs check_invariants at the barrier
    errs = [t for t in tickets if t.error is not None]
    assert errs and all(isinstance(t.error, InjectedDispatchError)
                        for t in errs)
    assert all(t.done and t.result is None for t in errs)
    st = server.stats
    assert st["shed"] == len(errs)
    assert st["served"] == len(reqs)


def test_retry_exhaustion_resolves_and_invariants_hold(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 8)
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None),
                            faults=FaultPlan(dispatch_fail_rate=1.0),
                            retry=RetryPolicy(max_attempts=3))
    tickets = [server.submit(n, p, _pump=False) for n, p in reqs]
    server.drain()
    assert all(isinstance(t.error, RetryExhaustedError) for t in tickets)
    assert all(t.attempts == 3 for t in tickets)
    assert all(isinstance(t.error.__cause__, InjectedDispatchError)
               for t in tickets)
    server.telemetry.check_invariants()      # exhausted != broken


def test_retry_absolute_deadline_counts_timeouts(lubm_served):
    queries, part = lubm_served
    ck = FakeClock()
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None,
                                                    clock=ck),
                            faults=FaultPlan(dispatch_fail_rate=1.0),
                            retry=RetryPolicy(max_attempts=50,
                                              deadline_ms=5.0))
    t = server.submit(queries[0].name, None, _pump=False)
    ck.advance(0.010)               # past the 5 ms absolute budget
    server.drain()
    assert isinstance(t.error, DeadlineExceededError)
    st = server.stats
    assert st["timeouts"] == 1 and st["shed"] == 1


def test_backoff_window_skips_pump_flushes(lubm_served):
    queries, part = lubm_served
    ck = FakeClock()
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=1.0,
                                                    max_batch=4, clock=ck),
                            faults=FaultPlan(dispatch_fail_rate=1.0,
                                             max_dispatch_failures=1),
                            retry=RetryPolicy(max_attempts=5, base_ms=2.0,
                                              cap_ms=2.0))
    t = server.submit(queries[0].name, None, _pump=False)
    ck.advance(0.002)               # deadline expires -> flush fails once
    server.pump()
    assert not t.done and server.stats["retries"] == 1
    server.pump()                   # still inside the backoff window
    assert not t.done and t.t_dispatch is None
    ck.advance(0.010)               # backoff (<= 2 ms jittered) elapsed
    server.pump()
    # this pump flushed the retry; its asynchronous dispatch need not be
    # ready yet, so the answer is delivered by the drain
    assert t.t_dispatch == ck() and server.stats["retries"] == 1
    server.drain()
    assert t.done and t.error is None


def test_fault_free_parity_with_empty_injector(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 12)
    plain = WorkloadServer(queries, part,
                           pipeline=PipelineConfig(deadline_ms=None))
    armed = WorkloadServer(queries, part,
                           pipeline=PipelineConfig(deadline_ms=None),
                           faults=FaultPlan(), retry=RetryPolicy())
    ra, rb = plain.serve(reqs), armed.serve(reqs)
    for a, b in zip(ra, rb):
        assert _eq(a, b)
    assert plain.stats == armed.stats


# ---- degraded mode --------------------------------------------------------

def test_shard_down_window_covered_exact_uncovered_typed(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 14)
    ck = FakeClock()
    server = WorkloadServer(
        queries, part,
        pipeline=PipelineConfig(deadline_ms=None, clock=ck),
        faults=FaultPlan(shard_down=((1, 1.0, 2.0),)))
    ref = server.serve(reqs)                   # healthy; arms the injector
    assert server.degraded is None

    ck.advance(1.5)                            # inside the down window
    got = server.serve(reqs)
    assert server.degraded == 1
    shed = server.shed_templates
    lost = uncovered_templates(queries, *degraded_placement(part, 1))
    assert shed == lost
    for (name, _), a, b in zip(reqs, ref, got):
        if name in shed:
            assert b is None
        else:
            assert _eq(a, b)                   # exact from re-homed rows
    st = server.stats
    assert st["shard_down"] == 1
    assert st["shed"] == sum(1 for n, _ in reqs if n in shed)

    ck.advance(1.0)                            # window closed -> restore
    back = server.serve(reqs)
    assert server.degraded is None and not server.shed_templates
    for a, b in zip(ref, back):
        assert _eq(a, b)


def test_mark_shard_down_sheds_queued_and_replicas_rehome(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 10)
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None))
    ref = server.serve(reqs)
    server.replicate_hot()                     # spare capacity for failover
    down = 1
    queued = [server.submit(n, p, _pump=False) for n, p in reqs]
    rep = server.mark_shard_down(down)
    shed = set(rep["shed_templates"])
    # queued uncovered tickets resolved immediately, typed
    for t in queued:
        if t.name in shed:
            assert t.done and isinstance(t.error, ShardDownError)
    with pytest.raises(RuntimeError, match="already degraded"):
        server.mark_shard_down(0)
    server.drain()
    for (name, _), a, t in zip(reqs, ref, queued):
        if name not in shed:
            assert t.error is None and _eq(a, t.result)
    if any(t.error is None for t in queued):
        assert server.stats["degraded_served"] > 0
    up = server.mark_shard_up()
    assert up["epoch"] == server.epoch and server.mark_shard_up() is None
    for a, b in zip(ref, server.serve(reqs)):
        assert _eq(a, b)


def test_submit_sheds_fast_while_degraded(lubm_served):
    queries, part = lubm_served
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None))
    rep = server.mark_shard_down(0)
    shed = rep["shed_templates"]
    if not shed:
        pytest.skip("every template covered around shard 0")
    t = server.submit(shed[0], None)
    assert t.done and isinstance(t.error, ShardDownError)
    assert t.result is None and t.flush_reason == "shed"
    assert server.queue_depth() == 0
    server.drain()


def test_migration_refused_while_degraded(lubm_served):
    queries, part = lubm_served
    from repro.adaptive.repartition import incremental_repartition
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None))
    server.mark_shard_down(2)
    res = incremental_repartition(part, queries,
                                  {q.name: 1.0 for q in queries},
                                  budget_frac=0.2)
    epoch = server.epoch
    with pytest.raises(MigrationAbortedError, match="refused"):
        server.migrate(res.part if res.mode != "noop" else part)
    assert server.epoch == epoch
    assert server.stats["migration_aborts"] == 1


# ---- transactional migration ----------------------------------------------

def test_migration_abort_rolls_back_old_epoch_serves(lubm_served):
    queries, part = lubm_served
    from repro.adaptive.repartition import incremental_repartition
    reqs = _stream(queries, 12)
    # answer_cache off: re-submitted requests must queue (not resolve at
    # submit from the cache) to exercise tickets crossing the aborted swap
    server = WorkloadServer(queries, part, answer_cache=False,
                            pipeline=PipelineConfig(deadline_ms=None),
                            faults=FaultPlan(abort_migrations=1))
    ref = server.serve(reqs)
    res = incremental_repartition(part, queries,
                                  {q.name: 1.0 for q in queries},
                                  budget_frac=0.25)
    assert res.mode != "noop"

    # tickets queued across the aborted swap: none lost, none duplicated
    queued = [server.submit(n, p, _pump=False) for n, p in reqs]
    with pytest.raises(MigrationAbortedError, match="injected"):
        server.migrate(res.part)
    assert server.epoch == 0                    # rollback: no swap
    assert server.stats["migration_aborts"] == 1
    assert server.queue_depth() == len(reqs)
    server.drain()
    assert all(t.done and t.error is None for t in queued)
    for a, t in zip(ref, queued):
        assert _eq(a, t.result)

    # the abort budget is spent: the same migration now commits
    mig = server.migrate(res.part)
    assert mig["epoch"] == 1 and server.epoch == 1
    for a, b in zip(ref, server.serve(reqs)):
        assert _eq(a, b)


def test_adaptive_controller_survives_injected_abort(lubm_served):
    queries, part = lubm_served
    from repro.adaptive.controller import AdaptiveConfig
    from repro.launch.serve import drifting_stream, two_phase_weights
    wa, wb = two_phase_weights(queries)
    stream = drifting_stream(queries, [(96, wa), (96, wb)], seed=0)
    cfg = AdaptiveConfig(window=64, check_every=32, min_requests=32)
    server = WorkloadServer(queries, part, adaptive=cfg,
                            faults=FaultPlan(abort_migrations=99),
                            pipeline=PipelineConfig(deadline_ms=None))
    for i in range(0, len(stream), 32):
        server.serve(stream[i:i + 32])          # must not raise
    assert server.epoch == 0                    # every prepare aborted
    aborted = [e for e in server.adaptive.events if e.mode == "aborted"]
    if server.faults.injected["migration_abort"]:
        assert aborted and all(e.migration is None for e in aborted)
        assert server.stats["migration_aborts"] == \
            server.faults.injected["migration_abort"]


# ---- EngineCache LRU -------------------------------------------------------

def test_engine_cache_lru_capacity_and_evictions(lubm_served):
    from repro.engine.batch import EngineCache, bucket_plans
    from repro.engine.planner import make_plan
    queries, part = lubm_served
    buckets = bucket_plans([make_plan(q, part) for q in queries])
    if len(buckets) < 3:
        pytest.skip("need >= 3 bucket signatures")
    cache = EngineCache(capacity=2)
    a, b, c = (bk.signature for bk in buckets[:3])
    cache.get(a), cache.get(b)
    assert len(cache) == 2 and cache.evictions == 0
    cache.get(a)                       # refresh a's LRU slot
    cache.get(c)                       # evicts b (least recent)
    assert len(cache) == 2 and cache.evictions == 1
    cache.get(a)
    assert cache.misses == 3           # a survived both rounds
    cache.get(b)                       # rebuild: it was evicted
    assert cache.misses == 4 and cache.evictions == 2
    with pytest.raises(ValueError, match="capacity"):
        EngineCache(capacity=0)
    assert EngineCache().capacity is None      # unbounded default


def test_engine_cache_evictions_published_to_registry(lubm_served):
    from repro.engine.batch import EngineCache
    queries, part = lubm_served
    server = WorkloadServer(queries, part, cache=EngineCache(capacity=1),
                            pipeline=PipelineConfig(deadline_ms=None))
    server.serve(_stream(queries, len(queries)))
    if server.cache.evictions:
        assert server.stats["engine_cache_evictions"] == \
            server.cache.evictions


# ---- graceful shutdown + edge drains ---------------------------------------

def test_shutdown_sheds_queued_with_typed_error(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 6)
    server = WorkloadServer(queries, part,
                            pipeline=PipelineConfig(deadline_ms=None))
    tickets = [server.submit(n, p, _pump=False) for n, p in reqs]
    out = server.shutdown(grace_s=0.0)
    assert out == {"drained": 0, "shed": len(reqs)}
    assert all(isinstance(t.error, ShutdownError) for t in tickets)
    assert server.queue_depth() == 0 and server.n_inflight == 0
    assert server.stats["shed"] == len(reqs)


def test_shutdown_with_grace_drains_everything(lubm_served):
    queries, part = lubm_served
    reqs = _stream(queries, 6)
    # answer_cache off so the re-submitted tickets actually queue
    server = WorkloadServer(queries, part, answer_cache=False,
                            pipeline=PipelineConfig(deadline_ms=None))
    ref = server.serve(reqs)
    tickets = [server.submit(n, p, _pump=False) for n, p in reqs]
    out = server.shutdown(grace_s=30.0)
    assert out["shed"] == 0 and out["drained"] == len(reqs)
    for a, t in zip(ref, tickets):
        assert t.error is None and _eq(a, t.result)


def test_empty_server_edge_paths(lubm_served):
    queries, part = lubm_served
    server = WorkloadServer(queries, part)
    ls = server.latency_stats()
    assert ls["n"] == 0 and ls["p99_ms"] == 0.0
    lsb = server.latency_stats(per_bucket=True)
    assert lsb["per_bucket"] == {}
    assert server.drain() == 0                 # invariants hold on empty
    assert server.pump() == 0
    assert server.shutdown() == {"drained": 0, "shed": 0}
    assert server.stats["served"] == 0
