"""KG query-serving driver — batched workload execution:

  python -m repro.launch.serve --dataset lubm --n-shards 3 --method wawpart \
      --batch 64

Builds the dataset, partitions it for its published workload, buckets the
query plans by shape (see engine/batch.py), compiles one engine per bucket,
and serves the request stream batch-by-batch, reporting throughput
(queries/sec) and the compile count per partitioning method.

--backend pallas executes every bucket engine's scan/join primitives
through the fused Pallas KG kernels (kernels/kg_scan, kernels/kg_join)
instead of dense jnp ops — bit-identical results, native kernels on TPU,
interpret mode elsewhere.

--adaptive closes the loop (repro.adaptive): the server tracks the live
template mix, detects drift against the mix the partitioning was computed
from, and migrates shards under a triple-movement budget between batches —
pair it with --drift, which serves a two-phase stream whose template mix
shifts halfway through.

--pipeline serves through the continuous-batching pipeline instead of
fixed synchronous batches: requests are submitted one by one with paced
arrivals (--arrival-ms), per-bucket queues flush when full or when the
oldest queued request's deadline budget (--deadline-ms) expires, and the
run reports p50/p95/p99 latency plus flush-reason counters. See
docs/architecture.md for the full request lifecycle.
"""
from __future__ import annotations

import argparse
import enum
import os
import time
from collections import OrderedDict, deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, NamedTuple

import numpy as np

from repro.adaptive.stats import WorkloadTracker, plan_shards
from repro.core.features import pattern_feature
from repro.obs import DEFAULT_CLOCK, Telemetry
from repro.core.partitioner import (Partitioning, centralized_partition,
                                    random_partition, wawpart_partition)
from repro.engine.batch import (EngineCache, bucket_collectives, bucket_plans,
                                canonical_params, dedup_requests,
                                extract_batch, extract_fanout, fetch_outputs,
                                pad_requests_pow2, shard_perms, stage_batch)
from repro.engine.federated import ShardedKG
from repro.engine.planner import make_plan
from repro.faults import (DeadlineExceededError, FaultInjector, FaultPlan,
                          MigrationAbortedError, RetryExhaustedError,
                          RetryPolicy, ShardDownError, ShutdownError,
                          classify, degraded_placement, uncovered_templates)
from repro.kg.generator import generate_bsbm, generate_lubm
from repro.kg.workloads import bsbm_queries, lubm_queries


class Counter(str, enum.Enum):
    """Every ``WorkloadServer.stats`` counter, by name.

    The single source of truth for the stats dict's keys — tests and
    benches import this instead of re-spelling strings (each member *is*
    its string value, so ``stats[Counter.SERVED]`` and ``stats["served"]``
    hit the same entry). Each counter's meaning is documented in
    docs/architecture.md ("Stats counters"). ``stats`` is the flat
    back-compat view; the labeled per-bucket/per-template series live in
    the server's ``telemetry`` registry (see docs/observability.md).
    """

    SERVED = "served"                  # requests delivered (hits + executed)
    EXECUTED = "executed"              # unique instances dispatched to engines
    DEDUPED = "deduped"                # requests collapsed onto an instance
    CACHE_HITS = "cache_hits"          # answer-cache hits (bypass the queue)
    CACHE_MISSES = "cache_misses"      # answer-cache lookups that missed
    FLUSH_FULL = "flush_full"          # dispatches cut by a full bucket queue
    FLUSH_DEADLINE = "flush_deadline"  # dispatches cut by a deadline expiry
    FLUSH_DRAIN = "flush_drain"        # dispatches cut by drain()/serve()
    RETRIES = "retries"                # tickets re-enqueued after a transient
    TIMEOUTS = "timeouts"              # tickets shed past their retry deadline
    SHED = "shed"                      # tickets resolved with a typed error
    DEGRADED_SERVED = "degraded_served"  # served exactly while a shard is down
    SHARD_DOWN = "shard_down"          # degraded-mode activations
    MIGRATION_ABORTS = "migration_aborts"  # migrate() prepares rolled back
    ENGINE_CACHE_EVICTIONS = "engine_cache_evictions"  # LRU engine evictions
    D2H_BYTES = "d2h_bytes"            # engine output bytes copied to host
    TABLE_ROWS_LIVE = "table_rows_live"  # mask-true rows of executed rows
    TABLE_ROWS_CAP = "table_rows_cap"  # executed rows x shards x table cap
    BATCH_ROWS_PADDED = "batch_rows_padded"  # power-of-two filler rows


@dataclass(frozen=True)
class PipelineConfig:
    """Continuous-batching pipeline knobs (see WorkloadServer.submit).

    deadline_ms: per-request latency budget — a bucket's queue is flushed
        partially filled once its oldest request has waited this long.
        ``None`` disables deadline flushes entirely (fill-only batching:
        a bucket dispatches only when full or drained).
    max_batch: queue length that triggers an immediate "full" flush.
    max_inflight: dispatched-but-unextracted batches kept outstanding —
        2 is classic double buffering (stage/submit batch k+1 while batch
        k computes on device); 1 degenerates to synchronous dispatch.
    clock: monotonic time source; injectable so tests drive deadlines
        deterministically without sleeping. The server's telemetry
        recorder adopts this clock, so trace spans, latency stats, and
        the CLI timing all share one timebase (obs.DEFAULT_CLOCK ==
        time.monotonic).
    """

    deadline_ms: float | None = 25.0
    max_batch: int = 64
    max_inflight: int = 2
    clock: Callable[[], float] = DEFAULT_CLOCK


@dataclass
class Ticket:
    """One submitted request's handle: result slot + lifecycle timestamps.

    ``submit()`` returns a Ticket immediately; ``done`` flips when the
    request's batch is extracted (or instantly on an answer-cache hit).
    The four timestamps are the pipeline's latency instrumentation:
    enqueue (submit), flush (queue cut into a batch), dispatch (engine
    call issued), done (results extracted) — ``latency_s`` is end-to-end.
    ``epoch`` records the serving epoch the request executed against and
    ``flush_reason`` which trigger cut its batch ("full" | "deadline" |
    "drain"; "hit" for answer-cache hits that never queued; "shed" for
    tickets resolved with a typed error before any dispatch).

    ``attempts`` counts dispatch attempts under a RetryPolicy; a ticket
    that exhausts its budget (or hits a permanent fault, its absolute
    retry deadline, or an uncovered degraded template) resolves with
    ``done=True``, ``result=None``, and the typed fault in ``error`` —
    callers distinguish answers from rejections by ``error is None``.
    """

    name: str
    params: np.ndarray | None
    seq: int
    t_enqueue: float
    deadline_s: float | None = None     # absolute; None = never expires
    t_flush: float | None = None
    t_dispatch: float | None = None
    t_done: float | None = None
    result: tuple | None = None
    done: bool = False
    epoch: int | None = None
    flush_reason: str | None = None
    cache_hit: bool = False
    attempts: int = 0
    error: Exception | None = None

    @property
    def latency_s(self) -> float:
        """End-to-end latency (enqueue -> done) in seconds."""
        if self.t_done is None:
            raise ValueError(f"request {self.name!r} is not done yet")
        return self.t_done - self.t_enqueue


class _Inflight(NamedTuple):
    """One dispatched-but-unextracted batch (the pipeline's device leg)."""
    bucket: object
    bi: int                           # bucket index (telemetry label/lane)
    tickets: list                     # Tickets in flush order
    unique: list                      # deduped (plan_idx, params) requests
    inverse: list | None              # fan-out map, None when dedup is off
    out: tuple                        # engine output (table, mask, overflow)
    epoch: int                        # serving epoch at dispatch
    degraded: bool = False            # dispatched while a shard was down


_UNSET = object()     # "use the config default" sentinel for submit()


class _ServingState(NamedTuple):
    """One partitioning epoch's immutable serving artifacts. serve() binds
    the state once per batch, so a migration swapping the server's state
    never changes tensors under an in-flight batch — it finishes against
    the epoch it started on."""
    epoch: int
    part: Partitioning
    kg: ShardedKG
    plans: dict                       # template name -> unpadded PhysicalPlan
    buckets: list
    route: dict                       # template name -> (bucket, idx)
    tr: object
    va: object
    perms: object
    shed: frozenset = frozenset()     # templates shed while degraded


class WorkloadServer:
    """Serve a stream of (query_name, params) requests with bucketed engines.

    Plans for the workload's template queries are built once, grouped into
    shape buckets, and each bucket's engine is compiled on first use (the
    `EngineCache` is shared across buckets and, if passed in, across servers,
    so identical bucket signatures — e.g. the same workload under two
    partitionings with equal capacities — reuse one compiled program).

    mesh=None serves through the vmap simulation (single device). Passing a
    mesh whose shard axis matches the partitioning routes every bucket
    through its shard_map engine instead: the KG tensors are placed
    shard-resident (one block per device, sharding/rules.kg_shardings) and
    cross-shard collectives appear only at the plan steps whose owner
    metadata marks a partition cut (`collective_counts`).

    dedup=True (default) collapses identical (template, params) requests
    within a batch to one scanned instance, fanned back out at delivery —
    `stats` tracks served/executed/deduped counts (see `Counter`).

    backend selects the engines' execution backend: "jnp" (dense XLA) or
    "pallas" (fused kg_scan/kg_join kernels; kernel_blocks sets their tile
    sizes). Results are bit-identical across backends on every serving
    path; the backend keys the EngineCache, so two servers sharing one
    cache with different backends never collide.

    adaptive=True (or an AdaptiveConfig) attaches an AdaptiveController
    (repro.adaptive): every routed request feeds a sliding-window workload
    tracker, drift checks run between batches, and a detected drift
    triggers a budgeted incremental repartition (or a full re-run on large
    drift) applied through `migrate()`. `epoch` counts applied migrations.

    answer_cache=True (default; or an int LRU capacity) memoizes final
    results by (template, canonical padded params): a repeat request skips
    engine dispatch entirely and returns the cached (solutions, count,
    overflow). The cache is epoch-versioned — any state swap (`migrate`,
    `replicate_hot`) bumps the serving epoch and the whole cache drops, so
    a stale pre-migration answer is never served. `stats` tracks
    cache_hits/cache_misses; warmup never reads or fills the cache.

    pipeline (a `PipelineConfig`) tunes the continuous-batching path:
    `submit()` enqueues one request into its bucket's queue and returns a
    `Ticket`; queues flush when full (max_batch), when the oldest queued
    request's deadline budget expires, or on `drain()`. Host-side batch
    assembly is overlapped with device compute via double-buffered staging
    (`engine/batch.stage_batch`, up to max_inflight outstanding batches).
    The synchronous `serve()` is a thin wrapper over submit+drain and
    returns bit-identical results to pre-pipeline serving.

    faults (a `FaultPlan` or `FaultInjector`, repro.faults) arms seeded
    deterministic fault injection: dispatch failures, flush delays,
    shard-down windows, and migration aborts. retry (a `RetryPolicy`)
    enables transient-failure recovery — failed flushes re-enqueue their
    surviving tickets at the queue front (epoch/seq order preserved) with
    exponential backoff + decorrelated jitter; exhausted tickets resolve
    to typed errors instead of poisoning drain(). Both default to None,
    and the fault-free fast path is byte-for-byte the pre-fault code:
    with faults=None and retry=None no try/except wraps the dispatch and
    results are bit-identical to a server built without these knobs.
    """

    ANSWER_CACHE_CAP = 65536

    def __init__(self, queries, part: Partitioning, *,
                 join_impl: str = "sorted", max_per_row: int | None = None,
                 gather_cap: int | None = None,
                 params_spec: dict[str, dict] | None = None,
                 cache: EngineCache | None = None,
                 mesh=None, dedup: bool = True, adaptive=None,
                 answer_cache: bool | int = True,
                 backend: str = "jnp", kernel_blocks=None,
                 pipeline: PipelineConfig | None = None,
                 telemetry: Telemetry | None = None,
                 faults: FaultPlan | FaultInjector | None = None,
                 retry: RetryPolicy | None = None):
        """Build the serving state for `part` and compile nothing yet.

        `telemetry` attaches an observability bundle (labeled metrics +
        trace recorder + profiler annotations, see repro.obs); omitted, a
        default all-off `Telemetry` still backs the `stats` counters. The
        recorder adopts the pipeline's injected clock.

        Raises ValueError on an unknown backend or invalid kernel_blocks
        (via `check_backend`); engine compilation happens lazily on the
        first dispatch that touches each bucket.
        """
        from repro.engine.primitives import check_backend
        self.queries = list(queries)
        self.join_impl = join_impl
        self.max_per_row = max_per_row
        self.gather_cap = gather_cap
        self.backend = backend
        self.kernel_blocks = check_backend(backend, kernel_blocks)
        self.cache = cache if cache is not None else EngineCache()
        self.mesh = mesh
        self.dedup = dedup
        self.params_spec = params_spec or {}
        self.pipeline = pipeline if pipeline is not None else PipelineConfig()
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self.telemetry.bind_clock(self.pipeline.clock)
        self._track = True
        self.answer_cache_cap = (self.ANSWER_CACHE_CAP if answer_cache is True
                                 else int(answer_cache))
        self._answers: OrderedDict[tuple, tuple] = OrderedDict()
        self._answers_epoch = 0
        self._cache_bypass = False
        self._queues: dict[int, list[Ticket]] = {}
        self._queues_epoch = 0
        self._inflight: deque[_Inflight] = deque()
        self._latencies: deque[tuple] = deque(maxlen=self.ANSWER_CACHE_CAP)
        self._seq = 0

        self.retry = retry
        if faults is None:
            self.faults = None
        elif isinstance(faults, FaultInjector):
            self.faults = faults
        else:
            self.faults = FaultInjector(faults)
        self._retry_after: dict[int, float] = {}   # bucket -> backoff until
        self._backoff_prev: dict[int, float] = {}  # bucket -> last backoff
        self._degraded: int | None = None          # down shard, if any
        self._pre_degraded: _ServingState | None = None
        self._evictions_seen = self.cache.evictions

        # live shard-load telemetry runs even without an adaptive
        # controller; when one attaches below, its tracker (sized by the
        # adaptive window) takes over via the `tracker` property
        self._tracker = WorkloadTracker()
        self.adaptive = None

        plans = {q.name: make_plan(q, part,
                                   params=self.params_spec.get(q.name))
                 for q in self.queries}
        self._state = self._build_state(0, part, ShardedKG.build(part), plans)
        self._refresh_obs()

        if adaptive is not None and adaptive is not False:
            from repro.adaptive.controller import (AdaptiveConfig,
                                                   AdaptiveController)
            cfg = adaptive if isinstance(adaptive, AdaptiveConfig) else None
            self.adaptive = AdaptiveController(self, cfg)

    # ---- state ---------------------------------------------------------

    def _build_state(self, epoch: int, part: Partitioning, kg: ShardedKG,
                     plans: dict, shed: frozenset = frozenset(),
                     ) -> _ServingState:
        import jax
        import jax.numpy as jnp

        buckets = bucket_plans([plans[q.name] for q in self.queries])
        route: dict[str, tuple[int, int]] = {}
        for bi, b in enumerate(buckets):
            for pi, plan in enumerate(b.plans):
                route[plan.query.name] = (bi, pi)
        tr, va = jnp.asarray(kg.triples), jnp.asarray(kg.valid)
        pe = jnp.asarray(shard_perms(kg))
        if self.mesh is not None:
            from repro.sharding.rules import kg_shardings
            tr, va, pe = (jax.device_put(a, s) for a, s in
                          zip((tr, va, pe), kg_shardings(self.mesh)))
        return _ServingState(epoch, part, kg, plans, buckets, route,
                             tr, va, pe, shed)

    @property
    def part(self) -> Partitioning:
        """The current epoch's partitioning."""
        return self._state.part

    @property
    def kg(self) -> ShardedKG:
        """The current epoch's sharded triple blocks."""
        return self._state.kg

    @property
    def buckets(self) -> list:
        """The current epoch's plan buckets (engine compilation units)."""
        return self._state.buckets

    @property
    def route(self) -> dict:
        """template name -> (bucket index, plan index) under this epoch."""
        return self._state.route

    @property
    def epoch(self) -> int:
        """Serving epoch: bumped by every migrate()/replicate_hot()
        (and by mark_shard_down()/mark_shard_up() transitions)."""
        return self._state.epoch

    @property
    def degraded(self) -> int | None:
        """The down shard the server is currently serving around, or
        None when every shard is healthy."""
        return self._degraded

    @property
    def shed_templates(self) -> frozenset:
        """Templates rejected under the current epoch (no live replica
        coverage while degraded); empty when healthy."""
        return self._state.shed

    @property
    def n_buckets(self) -> int:
        """Number of shape buckets (upper bound on compiles per epoch)."""
        return len(self._state.buckets)

    @property
    def n_compiles(self) -> int:
        """Engines built so far through this server's (shared) EngineCache."""
        return self.cache.misses

    @property
    def stats(self) -> dict[str, int]:
        """Flat counter totals keyed by `Counter` value — the historical
        stats-dict view, now backed by the telemetry registry (labels
        summed out; per-bucket/per-template series live in
        `telemetry.snapshot()`). Both ``stats[Counter.SERVED]`` and
        ``stats["served"]`` work, as before."""
        return {c.value: int(self.telemetry.total(c.value)) for c in Counter}

    def collective_counts(self) -> list[int]:
        """Per-bucket cross-shard gather sites in the compiled engines — the
        bucket-level WawPart cut counts (0 = collective-free program)."""
        return [bucket_collectives(b.signature) for b in self._state.buckets]

    @property
    def tracker(self) -> WorkloadTracker:
        """The live workload tracker feeding shard-load telemetry.

        The adaptive controller's tracker when one is attached (it sizes
        the window to the drift-check cadence), else the server's own
        always-on tracker — so `shard_requests` gauges are published
        whether or not adaptation is enabled.
        """
        if self.adaptive is not None:
            return self.adaptive.tracker
        return self._tracker

    def _refresh_obs(self) -> None:
        """Re-publish the state gauges (epoch, per-bucket cut collectives)
        for the current serving state; called at init and on every epoch
        bump since buckets can change count and signature. A bucket's
        `rank_sites` are published at its first dispatch of the epoch,
        once its engine is traced."""
        tele = self.telemetry
        tele.gauge("epoch", self._state.epoch)
        tele.registry["cut_collectives"].clear()
        tele.registry["rank_sites"].clear()
        self._rank_published: set[int] = set()
        for bi, b in enumerate(self._state.buckets):
            tele.gauge("cut_collectives", bucket_collectives(b.signature),
                       bucket=str(bi))
        self._refresh_shard_load()

    def _refresh_shard_load(self) -> None:
        """Publish live per-shard load gauges from the tracker window.

        `shard_requests{shard=s}` is the number of window requests whose
        routed plan touched shard s (a request spanning k shards counts
        once on each — exactly the load a cut join imposes), and
        `shard_load_imbalance` is their max/mean across all shards.
        The family is cleared first so a shard that fell out of the
        window (or a migration that changed the shard count) never
        leaves a stale gauge behind.
        """
        tele = self.telemetry
        snap = self.tracker.snapshot()
        n_shards = self._state.part.n_shards
        tele.registry["shard_requests"].clear()
        for s in range(n_shards):
            tele.gauge("shard_requests", snap.shard_load.get(s, 0),
                       shard=str(s))
        tele.gauge("shard_load_imbalance", snap.imbalance(n_shards))

    # ---- migration -----------------------------------------------------

    def _query_units(self, q, part: Partitioning) -> set:
        """Every data unit a query's patterns can touch under a placement —
        the same resolution make_plan routes through (routing_units)."""
        units: set = set()
        for pat in q.patterns:
            units.update(part.routing_units(pattern_feature(pat)))
        return units

    def migrate(self, new_part: Partitioning) -> dict:
        """Swap the server onto a new placement of the same store.

        Transactional: the whole next serving state is *prepared* first —
        KG deltas applied, plans rewritten, buckets rebuilt — and only
        then *committed* by the atomic epoch swap. Any exception during
        prepare rolls back cleanly (the old epoch keeps serving, no
        ticket is lost or duplicated, `migration_aborts` counts the
        rollback) and surfaces as `MigrationAbortedError` (ValueError for
        bad input passes through unchanged). Migration is refused while
        degraded — a placement computed against the healthy topology must
        not land while a shard is down.

        Sequencing per the migration contract:
          1. per-shard triple deltas applied to the ShardedKG (block
             capacity kept when the new shards still fit, so engines keep
             their input shapes);
          2. only plans whose data units moved are re-rewritten (same
             catalog; a full re-run's new catalog re-plans everything) —
             scan/table capacities are reused, they depend on data not
             placement;
          3. buckets rebuilt; the shared EngineCache keeps every bucket
             whose signature survived — only changed signatures compile;
          4. the epoch bumps and the serving state swaps atomically;
             in-flight batches hold the old state by reference, and
             *queued* (not yet flushed) pipeline requests re-route through
             the new epoch's buckets before their next dispatch — a
             post-migration flush never executes a stale-epoch plan.

        The epoch bump invalidates the whole answer cache (stale
        pre-migration answers are never served). Returns a report dict:
        epoch, n_moved, moved_fraction, plans_rewritten/reused,
        signatures_reused/new, cap_grew. Raises ValueError (via
        MigrationPlan.build) if `new_part` covers a different store.
        """
        from repro.adaptive.migrate import MigrationPlan

        st = self._state
        try:
            # ---- prepare: build the entire next state off to the side
            if self._degraded is not None:
                raise MigrationAbortedError(
                    f"migration refused while shard {self._degraded} is "
                    f"down (degraded placement is temporary)")
            mig = MigrationPlan.build(st.part, new_part)
            kg = mig.apply_kg(st.kg, new_part)
            if self.faults is not None:
                self.faults.check_migration_abort()

            same_catalog = new_part.catalog is st.part.catalog
            moved_units = set()
            if same_catalog:
                keys = set(st.part.unit_shard) | set(new_part.unit_shard)
                moved_units = {u for u in keys
                               if st.part.unit_shard.get(u)
                               != new_part.unit_shard.get(u)}
            plans: dict = {}
            rewritten = 0
            for q in self.queries:
                old_plan = st.plans[q.name]
                # same catalog => same unit_shard key set (incremental moves
                # reassign values only), so one placement's resolution covers
                # both sides of the move
                if same_catalog and not self._query_units(q, new_part) \
                        & moved_units:
                    plans[q.name] = old_plan
                    continue
                caps = ([s.scan_cap for s in old_plan.steps],
                        old_plan.table_cap)
                plans[q.name] = make_plan(q, new_part,
                                          params=self.params_spec.get(q.name),
                                          capacities=caps)
                rewritten += 1

            new_state = self._build_state(st.epoch + 1, new_part, kg, plans)
        except Exception as exc:
            # ---- rollback: nothing was swapped; old epoch keeps serving
            self.telemetry.count("migration_aborts")
            self.telemetry.trace.instant(
                "migration_abort", args={"epoch": st.epoch,
                                         "error": type(exc).__name__})
            if isinstance(exc, (MigrationAbortedError, ValueError)):
                raise
            raise MigrationAbortedError(
                f"migration prepare failed: {exc}") from exc

        # ---- commit: the atomic swap (nothing below can throw partway)
        old_sigs = {b.signature for b in st.buckets}
        new_sigs = {b.signature for b in new_state.buckets}
        self._state = new_state
        self._answers.clear()        # every cached answer is pre-migration
        self._answers_epoch = new_state.epoch
        self._refresh_obs()
        self.telemetry.count("epoch_bumps", kind="migrate")
        self.telemetry.trace.instant(
            "migration", args={"epoch": new_state.epoch,
                               "n_moved": mig.n_moved,
                               "plans_rewritten": rewritten})
        return {"epoch": new_state.epoch, "n_moved": mig.n_moved,
                "moved_fraction": mig.moved_fraction,
                "plans_rewritten": rewritten,
                "plans_reused": len(self.queries) - rewritten,
                "signatures_reused": len(new_sigs & old_sigs),
                "signatures_new": len(new_sigs - old_sigs),
                "cap_grew": kg.cap > st.kg.cap}

    # ---- hot cut-edge replication --------------------------------------

    def replicate_hot(self, query_weights: dict[str, float] | None = None, *,
                      top_k: int = 4, budget_frac: float = 0.25) -> dict:
        """Replicate the workload's hottest safe cut features onto their
        queries' primary shards, removing those cross-shard gathers.

        query_weights defaults to the adaptive tracker's live window (when
        attached and non-empty), then the partitioning's recorded workload
        weights, then uniform; top_k bounds how many candidates are taken
        and budget_frac bounds replicated triples as a fraction of the
        store. Sequencing mirrors `migrate`: the ShardedKG is rebuilt with
        replica rows appended (old block capacity kept when they fit in
        the padding, so unchanged engines keep their shapes), only the
        affected queries re-plan (capacities reused), and the epoch bump
        atomically swaps the state, drops the answer cache, and re-routes
        any queued pipeline requests. Results stay bit-identical —
        replication only changes *where* a step's rows are read, never
        which rows exist (see Partitioning.can_replicate for the
        no-double-count rule).

        Returns a report dict: epoch, replicated_units/_triples,
        plans_rewritten, queries_affected, collectives_before/_after
        (per-bucket), cap_grew.
        """
        from repro.adaptive.replicate import plan_hot_replication

        st = self._state
        if query_weights is None and self.adaptive is not None:
            snap = self.adaptive.tracker.snapshot()
            if snap.total:
                query_weights = dict(snap.counts)
        if query_weights is None:
            # falls through to uniform when the partitioning was built
            # without a recorded workload mix (meta stores {} then)
            query_weights = st.part.meta.get("query_weights") or None

        report = plan_hot_replication(st.part, self.queries, query_weights,
                                      top_k=top_k, budget_frac=budget_frac)
        before = self.collective_counts()
        out = {"epoch": st.epoch, "replicated_units": 0,
               "replicated_triples": 0, "plans_rewritten": 0,
               "queries_affected": [],
               "collectives_before": before, "collectives_after": before,
               "cap_grew": False}
        if not report.replicas:
            return out

        new_part = st.part.with_replicas(report.replicas)
        kg = ShardedKG.build(new_part, min_cap=st.kg.cap)
        affected = {name for c in report.chosen for name in c.queries}
        plans: dict = {}
        rewritten = 0
        for q in self.queries:
            old_plan = st.plans[q.name]
            if q.name not in affected:
                plans[q.name] = old_plan
                continue
            caps = ([s.scan_cap for s in old_plan.steps], old_plan.table_cap)
            plans[q.name] = make_plan(q, new_part,
                                      params=self.params_spec.get(q.name),
                                      capacities=caps)
            rewritten += 1

        new_state = self._build_state(st.epoch + 1, new_part, kg, plans)
        self._state = new_state
        self._answers.clear()        # pre-replication answers are stale
        self._answers_epoch = new_state.epoch
        self._refresh_obs()
        self.telemetry.count("epoch_bumps", kind="replicate")
        self.telemetry.trace.instant(
            "replication", args={"epoch": new_state.epoch,
                                 "replicated_triples": report.total_triples})
        out.update(
            epoch=new_state.epoch,
            replicated_units=sum(len(ts) for ts in report.replicas.values()),
            replicated_triples=report.total_triples,
            plans_rewritten=rewritten,
            queries_affected=sorted(affected),
            collectives_after=self.collective_counts(),
            cap_grew=kg.cap > st.kg.cap)
        return out

    # ---- degraded mode (shard down) -------------------------------------

    def mark_shard_down(self, shard: int) -> dict:
        """Enter degraded mode: serve around `shard` using live replicas.

        Builds the degraded primary-only placement (repro.faults
        `degraded_placement`: units homed on the down shard re-home onto
        a live replica holder), re-plans every still-coverable template
        with the down shard forbidden as the plan's primary (`make_plan
        forbid_ppn` — capacities reused, so surviving bucket signatures
        keep their compiled engines), and swaps the state under a new
        epoch. Covered templates keep serving *exactly* — the same rows
        exist, on live shards. Templates needing a unit whose only copy
        was on the down shard go into the state's `shed` set: queued
        tickets for them resolve immediately with `ShardDownError`, and
        new submits shed fast without ever queueing.

        The pre-degraded state is saved verbatim for `mark_shard_up()`.
        Raises RuntimeError if already degraded (one down shard at a
        time) and ValueError for a shard outside the placement. Returns
        a report dict: epoch, shard, shed_templates, lost_units,
        rehomed_units.
        """
        if self._degraded is not None:
            raise RuntimeError(f"already degraded (shard {self._degraded} "
                               f"down); mark_shard_up() first")
        st = self._state
        tele = self.telemetry
        dpart, lost = degraded_placement(st.part, shard)
        shed = uncovered_templates(self.queries, dpart, lost)
        rehomed = sum(1 for u, s in st.part.unit_shard.items()
                      if s == shard and dpart.unit_shard[u] != shard)
        plans: dict = {}
        for q in self.queries:
            old_plan = st.plans[q.name]
            if q.name in shed:
                # kept so buckets/route still cover the template (the
                # shed check fires before any dispatch can reach it)
                plans[q.name] = old_plan
                continue
            caps = ([s.scan_cap for s in old_plan.steps], old_plan.table_cap)
            plans[q.name] = make_plan(q, dpart,
                                      params=self.params_spec.get(q.name),
                                      capacities=caps,
                                      forbid_ppn=frozenset({shard}))
        kg = ShardedKG.build(dpart, min_cap=st.kg.cap)
        new_state = self._build_state(st.epoch + 1, dpart, kg, plans,
                                      shed=shed)
        self._pre_degraded = st
        self._degraded = shard
        self._state = new_state
        self._answers.clear()      # cached answers assume the healthy epoch
        self._answers_epoch = new_state.epoch
        self._retry_after.clear()  # backoff lanes are per-epoch buckets
        self._backoff_prev.clear()
        self._refresh_obs()
        tele.count("epoch_bumps", kind="degrade")
        tele.count("shard_down", shard=str(shard))
        tele.trace.instant("shard_down",
                           args={"shard": shard, "epoch": new_state.epoch,
                                 "shed_templates": len(shed)})
        # already-queued tickets for uncovered templates shed now — they
        # can never dispatch under this epoch
        self._sync_queues()
        for bi in list(self._queues):
            keep = [t for t in self._queues[bi] if t.name not in shed]
            for t in self._queues[bi]:
                if t.name in shed:
                    self._resolve_error(
                        t, ShardDownError(
                            f"template {t.name!r} has no live replica "
                            f"coverage with shard {shard} down"), bi=bi)
            if keep:
                self._queues[bi] = keep
            else:
                del self._queues[bi]
            tele.gauge("queue_depth", len(keep), bucket=str(bi))
        return {"epoch": new_state.epoch, "shard": shard,
                "shed_templates": sorted(shed), "lost_units": len(lost),
                "rehomed_units": rehomed}

    def mark_shard_up(self) -> dict | None:
        """Leave degraded mode: restore the saved healthy state.

        The pre-degraded placement, KG, and plans swap back under a new
        epoch (`epoch_bumps{kind=restore}`) — bucket signatures match the
        healthy ones, so the EngineCache serves every engine without a
        recompile. Queued tickets re-route lazily (`_sync_queues`), the
        answer cache drops (degraded-epoch answers are fine but the
        epoch-version contract is one cache per epoch). No-op returning
        None when not degraded.
        """
        if self._degraded is None:
            return None
        saved = self._pre_degraded
        st = self._state
        new_state = self._build_state(st.epoch + 1, saved.part, saved.kg,
                                      saved.plans)
        self._state = new_state
        self._degraded = None
        self._pre_degraded = None
        self._answers.clear()
        self._answers_epoch = new_state.epoch
        self._retry_after.clear()
        self._backoff_prev.clear()
        self._refresh_obs()
        self.telemetry.count("epoch_bumps", kind="restore")
        self.telemetry.trace.instant("shard_up",
                                     args={"epoch": new_state.epoch})
        return {"epoch": new_state.epoch}

    def _poll_faults(self, now: float) -> None:
        """Drive injector-scheduled shard-down windows off the clock.

        Called at the top of submit/pump/drain: enters degraded mode when
        a window opens, restores when it closes (windows are relative to
        the injector's arming — its first poll).
        """
        inj = self.faults
        if inj is None or not inj.enabled:
            return
        down = inj.shard_down_now(now)
        if down == self._degraded:
            return
        if self._degraded is not None:
            self.mark_shard_up()
        if down is not None:
            inj.injected["shard_down"] += 1
            self.mark_shard_down(down)

    # ---- continuous-batching pipeline ----------------------------------

    def submit(self, name: str, params: np.ndarray | None = None, *,
               deadline_ms=_UNSET, _pump: bool = True) -> Ticket:
        """Enqueue one request into its bucket's queue; returns a Ticket.

        The request is routed (feeding the adaptive tracker), checked
        against the answer cache — a hit bypasses the queue entirely and
        returns an already-done Ticket whose latency is still stamped —
        and otherwise appended to its bucket's queue. deadline_ms
        overrides the pipeline config's budget for this request (None =
        never deadline-flush it); the queue dispatches when it reaches
        max_batch ("full"), when its oldest request's budget expires
        ("deadline", checked in pump()), or on drain().

        Raises KeyError for a template name outside the workload and
        ValueError for a param vector wider than the bucket executes with.

        While degraded (a shard down), a template in the state's shed set
        returns an already-done Ticket carrying a `ShardDownError` — the
        fast typed rejection — instead of queueing work that could never
        dispatch exactly.
        """
        now = self.pipeline.clock()
        self._poll_faults(now)
        self._sync_queues()
        bi, pi = self._state.route[name]
        # the span covers the server's own work for the ticket (tracker,
        # cache lookup, enqueue) and ends before the nested pump()
        with self.telemetry.span("submit", f"bucket{bi}"):
            ticket = self._admit(name, params, deadline_ms, now, bi, pi)
        if _pump and not ticket.done:
            self.pump()
        return ticket

    def _admit(self, name: str, params, deadline_ms, now: float, bi: int,
               pi: int) -> Ticket:
        """`submit`'s work after routing: feed the tracker, then answer
        from the answer cache, shed, or enqueue into bucket bi."""
        st = self._state
        tele = self.telemetry
        plan = st.buckets[bi].plans[pi]
        # cache hits still feed the tracker: drift detection must see
        # the real mix even at high hit rates
        if self._track:
            if self.adaptive is not None:
                self.adaptive.record(name, plan)
            else:
                self._tracker.observe(name, cut_joins=len(plan.cut_steps),
                                      shards=plan_shards(plan))
            if plan.cut_steps:
                tele.count("observed_cut_joins", len(plan.cut_steps),
                           template=name)
        # validate params eagerly — an oversized vector must fail at
        # submit, not at a deadline flush long after the caller moved on
        key = (name, canonical_params(params, st.buckets[bi].n_params))

        budget = self.pipeline.deadline_ms if deadline_ms is _UNSET \
            else deadline_ms
        ticket = Ticket(name=name, params=params, seq=self._seq,
                        t_enqueue=now,
                        deadline_s=None if budget is None
                        else now + budget / 1e3)
        self._seq += 1

        if st.shed and name in st.shed:
            self._resolve_error(
                ticket, ShardDownError(
                    f"template {name!r} has no live replica coverage "
                    f"with shard {self._degraded} down"), bi=bi)
            return ticket

        if self._answers and self._answers_epoch != st.epoch:
            self._answers.clear()
        self._answers_epoch = st.epoch
        if self.answer_cache_cap > 0 and not self._cache_bypass:
            hit = self._answers.get(key)
            if hit is not None:
                self._answers.move_to_end(key)
                ticket.result = hit
                ticket.done = True
                ticket.cache_hit = True
                ticket.flush_reason = "hit"
                ticket.epoch = st.epoch
                ticket.t_flush = ticket.t_dispatch = ticket.t_done = \
                    self.pipeline.clock()
                tele.count("served", template=name)
                tele.count("cache_hits", template=name)
                tele.observe("request_latency_ms",
                             (ticket.t_done - ticket.t_enqueue) * 1e3)
                if tele.trace.enabled:
                    span = f"ticket/{name}"
                    tele.trace.async_begin(span, ticket.seq,
                                           ts=ticket.t_enqueue,
                                           args={"cache_hit": True,
                                                 "epoch": st.epoch})
                    tele.trace.async_end(span, ticket.seq,
                                         ts=ticket.t_done)
                self._latencies.append((bi, ticket.t_enqueue, ticket.t_flush,
                                        ticket.t_dispatch, ticket.t_done))
                return ticket
            tele.count("cache_misses", template=name)

        self._queues.setdefault(bi, []).append(ticket)
        tele.gauge("queue_depth", len(self._queues[bi]), bucket=str(bi))
        return ticket

    def pump(self) -> int:
        """Advance the pipeline without blocking on new work.

        Cuts every full queue into "full" flushes (max_batch at a time),
        deadline-flushes every bucket whose oldest queued request's budget
        has expired (the *partial* bucket dispatch that bounds tail
        latency), and retires in-flight batches whose device results are
        ready. Returns the number of requests completed by this call.
        Drives the adaptive drift check after completions, mirroring the
        synchronous path's between-batches cadence.

        A bucket inside its retry backoff window (a transient dispatch
        failure re-enqueued its tickets) or an injected flush-delay
        window is skipped this pump — its tickets dispatch on a later
        pump or at drain().
        """
        now = self.pipeline.clock()
        self._poll_faults(now)
        self._sync_queues()
        before = int(self.telemetry.total("served"))
        for bi in list(self._queues):
            while (len(self._queues.get(bi, ())) >= self.pipeline.max_batch
                   and not self._in_backoff(bi, now)
                   and not (self.faults is not None
                            and self.faults.flush_delayed(bi, now))):
                self._flush(bi, "full", now, limit=self.pipeline.max_batch)
        for bi in list(self._queues):
            q = self._queues.get(bi)
            if not q or self._in_backoff(bi, now):
                continue
            if self.faults is not None and \
                    self.faults.flush_delayed(bi, now):
                continue
            due = min((t.deadline_s for t in q if t.deadline_s is not None),
                      default=None)
            if due is not None and now >= due:
                self._flush(bi, "deadline", now)
        self._retire()
        self.telemetry.gauge("inflight", len(self._inflight))
        self._refresh_shard_load()
        done = int(self.telemetry.total("served")) - before
        if done and self.adaptive is not None and self._track:
            self.adaptive.maybe_adapt()
        return done

    def drain(self) -> int:
        """Flush every queued request and retire all in-flight batches.

        The shutdown/sync barrier: after drain() returns, every submitted
        Ticket is done, `queue_depth()` is 0, and nothing is in flight.
        Each bucket's remaining queue dispatches as one batch (reason
        "drain", however partial). Returns the number of requests
        completed by this call. With everything settled, the telemetry
        counter invariants from docs/architecture.md are enforced
        (`Telemetry.check_invariants`) — a RuntimeError here means a
        serving-path accounting bug, not bad user input.

        Under fault injection / retry, drain ignores backoff and
        flush-delay windows (it is the barrier) and keeps flushing until
        the queues are empty: every re-enqueued ticket either dispatches
        successfully or exhausts its attempts into a typed error, so
        termination is bounded by the retry budget.
        """
        now = self.pipeline.clock()
        self._poll_faults(now)
        self._sync_queues()
        before = int(self.telemetry.total("served"))
        rounds = 0
        while self._queues:
            now = self.pipeline.clock()
            for bi in list(self._queues):
                if self._queues.get(bi):
                    self._flush(bi, "drain", now)
            rounds += 1
            if rounds > 100_000:
                raise RuntimeError("drain() made no progress after "
                                   "100000 flush rounds")
        while self._inflight:
            self._complete(self._inflight.popleft(), "drain")
        self.telemetry.gauge("inflight", 0)
        self._refresh_shard_load()
        self.telemetry.check_invariants()
        return int(self.telemetry.total("served")) - before

    def shutdown(self, grace_s: float = 2.0) -> dict:
        """Graceful-shutdown barrier with a bounded grace budget.

        Tries to drain normally for up to `grace_s` seconds on the
        pipeline clock (backoff and delay windows are ignored, like
        drain); once the budget expires — or immediately when
        ``grace_s <= 0`` — every still-queued ticket resolves with a
        typed `ShutdownError` (counted as shed, so the telemetry
        invariants hold for the partial run). In-flight batches always
        complete: their work is already on the device. Returns
        {"drained": n, "shed": n}; the invariants are checked before
        returning, exactly as a full drain would.
        """
        clock = self.pipeline.clock
        deadline = clock() + max(0.0, grace_s)
        before = int(self.telemetry.total("served"))
        self._sync_queues()
        if grace_s > 0:
            rounds = 0
            while self._queues and clock() < deadline:
                now = clock()
                for bi in list(self._queues):
                    if self._queues.get(bi):
                        self._flush(bi, "drain", now)
                    if clock() >= deadline:
                        break
                rounds += 1
                if rounds > 100_000:
                    break
        shed_n = 0
        for bi in list(self._queues):
            for t in self._queues.pop(bi):
                self._resolve_error(
                    t, ShutdownError("server shutting down"), bi=bi)
                shed_n += 1
            self.telemetry.gauge("queue_depth", 0, bucket=str(bi))
        while self._inflight:
            self._complete(self._inflight.popleft(), "drain")
        self.telemetry.gauge("inflight", 0)
        self._refresh_shard_load()
        self.telemetry.check_invariants()
        drained = int(self.telemetry.total("served")) - before - shed_n
        return {"drained": drained, "shed": shed_n}

    def queue_depth(self) -> int:
        """Requests enqueued but not yet flushed into a dispatch."""
        return sum(len(q) for q in self._queues.values())

    @property
    def n_inflight(self) -> int:
        """Batches dispatched to the device but not yet extracted."""
        return len(self._inflight)

    _LATENCY_KEYS = ("p50_ms", "p95_ms", "p99_ms", "mean_ms", "max_ms",
                     "queue_p99_ms", "service_p99_ms")

    @classmethod
    def _percentiles(cls, rows: list[tuple]) -> dict:
        """Percentile block for one group of (bi, te, tf, td, tdone) rows.

        Rows missing the flush stamp still contribute end-to-end latency
        but are excluded from the queue/service leg split (a ticket can
        only lack stamps if it was surfaced before its flush — the legs
        would be meaningless for it).
        """
        te = np.asarray([r[1] for r in rows])
        tdone = np.asarray([r[4] for r in rows])
        total = (tdone - te) * 1e3
        out = {"n": len(rows),
               "p50_ms": float(np.percentile(total, 50)),
               "p95_ms": float(np.percentile(total, 95)),
               "p99_ms": float(np.percentile(total, 99)),
               "mean_ms": float(total.mean()),
               "max_ms": float(total.max()),
               "queue_p99_ms": 0.0, "service_p99_ms": 0.0}
        staged = [r for r in rows if r[2] is not None]
        if staged:
            queue = np.asarray([(r[2] - r[1]) for r in staged]) * 1e3
            service = np.asarray([(r[4] - r[2]) for r in staged]) * 1e3
            out["queue_p99_ms"] = float(np.percentile(queue, 99))
            out["service_p99_ms"] = float(np.percentile(service, 99))
        return out

    def latency_stats(self, *, per_bucket: bool = False) -> dict:
        """Latency percentiles over the recorded request lifecycle stamps.

        Covers every request completed since the last reset_stats()
        (answer-cache hits included — their latency is the submit
        round-trip). Returns n plus p50/p95/p99/mean/max end-to-end
        latency in ms, and p99 of the queue (enqueue->flush) and service
        (flush->done) legs; all zeros when nothing was recorded. Rows
        missing enqueue/done stamps are skipped; rows missing only the
        flush stamp fall out of the leg percentiles (see _percentiles).

        per_bucket=True additionally returns a ``"per_bucket"`` dict
        mapping bucket index to the same percentile block over just that
        bucket's requests — off by default since the grouping pass costs
        a full scan of the latency window.
        """
        rows = [r for r in self._latencies
                if r[1] is not None and r[4] is not None]
        if not rows:
            out = {"n": 0, **{k: 0.0 for k in self._LATENCY_KEYS}}
            if per_bucket:
                out["per_bucket"] = {}
            return out
        out = self._percentiles(rows)
        if per_bucket:
            by_bucket: dict[int, list[tuple]] = {}
            for r in rows:
                by_bucket.setdefault(r[0], []).append(r)
            out["per_bucket"] = {bi: self._percentiles(rs)
                                 for bi, rs in sorted(by_bucket.items())}
        return out

    def _sync_queues(self) -> None:
        """Re-route queued requests after an epoch bump (lazy).

        migrate()/replicate_hot() rebuild the buckets, so queue keys
        (bucket indices) and plan routing may be stale. Queued tickets are
        re-enqueued through the *new* epoch's route in submission order,
        keeping their original enqueue timestamps and deadlines — a flush
        after the bump can therefore never dispatch a stale-epoch plan.
        In-flight batches are untouched: they already dispatched and
        finish against the epoch they started on.
        """
        if self._queues_epoch == self._state.epoch:
            return
        pending = sorted((t for q in self._queues.values() for t in q),
                         key=lambda t: t.seq)
        self._queues = {}
        for t in pending:
            bi, _ = self._state.route[t.name]
            self._queues.setdefault(bi, []).append(t)
        self._queues_epoch = self._state.epoch

    def _flush(self, bi: int, reason: str, now: float,
               limit: int | None = None) -> None:
        """Cut (up to limit of) bucket bi's queue into one engine dispatch.

        Stamps flush/dispatch times, dedups, pads the batch axis to a
        power of two (noop fillers), stages the batch onto the device
        (overlapped transfer), issues the asynchronous engine call, and
        enqueues the in-flight record. Completes the oldest in-flight
        batch synchronously when max_inflight would be exceeded — the
        pipeline's backpressure.
        """
        q = self._queues[bi]
        take, rest = (q[:limit], q[limit:]) if limit is not None \
            else (q, [])
        if rest:
            self._queues[bi] = rest
        else:
            del self._queues[bi]

        st = self._state
        tele = self.telemetry
        bucket = st.buckets[bi]
        b_lab = str(bi)
        tele.gauge("queue_depth", len(rest), bucket=b_lab)
        tele.count(f"flush_{reason}", bucket=b_lab)
        tele.observe("batch_fill_ratio",
                     len(take) / self.pipeline.max_batch, bucket=b_lab)
        for t in take:
            t.t_flush = now
            t.flush_reason = reason
        reqs = [(st.route[t.name][1], t.params) for t in take]
        if self.dedup:
            unique, inverse = dedup_requests(reqs, bucket.n_params)
        else:
            unique, inverse = reqs, None
        tele.observe("dedup_fanout", len(take) / len(unique), bucket=b_lab)
        fn = self._engine(bucket)
        lane = f"bucket{bi}"
        with tele.span(f"flush/{reason}", lane, n=len(take),
                       unique=len(unique), epoch=st.epoch):
            if self.faults is None and self.retry is None:
                # fault-free fast path: byte-for-byte the pre-fault dispatch
                out, n_pad = self._stage_and_call(fn, bucket, unique, lane)
            else:
                try:
                    if self.faults is not None:
                        self.faults.on_dispatch(bi)
                    out, n_pad = self._stage_and_call(fn, bucket, unique,
                                                      lane)
                except Exception as exc:
                    self._flush_failed(bi, take, exc, now)
                    return
            t_dispatch = self.pipeline.clock()
        tele.count("batch_rows_padded", n_pad, bucket=b_lab)
        if bi not in self._rank_published:      # the engine is traced now
            self._rank_published.add(bi)
            for method, n in fn.rank_sites.items():
                tele.gauge("rank_sites", n, bucket=b_lab, method=method)
        for t in take:
            t.t_dispatch = t_dispatch
            t.epoch = st.epoch
        self._inflight.append(_Inflight(bucket, bi, take, unique, inverse,
                                        out, st.epoch,
                                        self._degraded is not None))
        while len(self._inflight) > self.pipeline.max_inflight:
            self._complete(self._inflight.popleft(), "backpressure")
        tele.gauge("inflight", len(self._inflight))

    def _stage_and_call(self, fn, bucket, unique: list, lane: str):
        """Stage the power-of-two padded batch and issue the engine call
        (asynchronous); returns (engine output, filler rows added)."""
        st = self._state
        tele = self.telemetry
        with tele.span("stage", lane):
            padded = pad_requests_pow2(unique)
            pd, params = stage_batch(bucket, padded, mesh=self.mesh)
        with tele.span("dispatch", lane):
            out = fn(st.tr, st.va, st.perms, pd, params)
        return out, len(padded) - len(unique)

    def _in_backoff(self, bi: int, now: float) -> bool:
        """Whether bucket bi sits inside a retry backoff window."""
        return self._retry_after.get(bi, 0.0) > now

    def _resolve_error(self, ticket: Ticket, err: Exception, *, bi: int,
                       timeout: bool = False) -> None:
        """Resolve one ticket to a typed error result (counted as shed).

        The ticket completes like any served request — done flips, the
        latency is observed, the trace span closes — but `result` stays
        None and `error` carries the typed fault. `served` still counts
        it (the request got a definitive answer: a rejection), keeping
        the invariant served == cache_hits + executed + deduped + shed.
        """
        now = self.pipeline.clock()
        ticket.error = err
        ticket.result = None
        ticket.done = True
        ticket.epoch = self._state.epoch
        ticket.t_done = now
        if ticket.flush_reason is None:
            ticket.flush_reason = "shed"
        tele = self.telemetry
        tele.count("served", template=ticket.name)
        tele.count("shed", template=ticket.name)
        if timeout:
            tele.count("timeouts", template=ticket.name)
        tele.observe("request_latency_ms",
                     (now - ticket.t_enqueue) * 1e3)
        if tele.trace.enabled:
            span = f"ticket/{ticket.name}"
            tele.trace.async_begin(span, ticket.seq, ts=ticket.t_enqueue,
                                   args={"error": type(err).__name__,
                                         "epoch": ticket.epoch})
            tele.trace.async_end(span, ticket.seq, ts=now)
        self._latencies.append((bi, ticket.t_enqueue, ticket.t_flush,
                                ticket.t_dispatch, now))

    def _flush_failed(self, bi: int, take: list[Ticket], exc: Exception,
                      now: float) -> None:
        """Recover from a failed dispatch of bucket bi's cut tickets.

        Classification (repro.faults.classify) splits the world in two:
        a *permanent* fault (CapacityOverflowError, bad-input errors) —
        or any fault with no RetryPolicy attached — resolves every ticket
        in the cut to a typed error immediately. A *transient* fault
        re-enqueues the surviving tickets at the *front* of the bucket's
        queue (their seq order is preserved, so epoch ordering and
        re-routing stay correct) and arms an exponential backoff +
        decorrelated jitter window for the bucket; tickets past the
        policy's absolute deadline resolve as timeouts, tickets out of
        attempts as RetryExhaustedError.
        """
        tele = self.telemetry
        kind = classify(exc)
        if tele.trace.enabled:
            tele.trace.instant("dispatch_fault",
                               args={"bucket": bi, "kind": kind,
                                     "error": type(exc).__name__})
        policy = self.retry
        if kind == "permanent" or policy is None:
            for t in take:
                t.attempts += 1
                self._resolve_error(t, exc, bi=bi)
            return
        survivors: list[Ticket] = []
        for t in take:
            t.attempts += 1
            hard = None if policy.deadline_ms is None \
                else t.t_enqueue + policy.deadline_ms / 1e3
            if hard is not None and now >= hard:
                self._resolve_error(
                    t, DeadlineExceededError(
                        f"{t.name!r} past its {policy.deadline_ms:g} ms "
                        f"retry deadline after {t.attempts} attempts"),
                    bi=bi, timeout=True)
            elif t.attempts >= policy.max_attempts:
                err = RetryExhaustedError(
                    f"{t.attempts} dispatch attempts failed for "
                    f"{t.name!r}: {exc}")
                err.__cause__ = exc
                self._resolve_error(t, err, bi=bi)
            else:
                survivors.append(t)
        if not survivors:
            return
        tele.count("retries", len(survivors), bucket=str(bi))
        # front of the queue: a retried ticket never reorders behind
        # requests submitted after it (take was cut in seq order)
        self._queues[bi] = survivors + self._queues.get(bi, [])
        tele.gauge("queue_depth", len(self._queues[bi]), bucket=str(bi))
        back = policy.backoff_s(max(t.attempts for t in survivors),
                                self._backoff_prev.get(bi))
        self._backoff_prev[bi] = back
        self._retry_after[bi] = now + back

    def _retire(self) -> int:
        """Complete in-flight batches whose device results are ready.

        Only the queue head is eligible (completion order == dispatch
        order); readiness is polled without blocking, so a pump() between
        paced arrivals retires finished work early and keeps result
        latency from being deferred to the next flush or drain.
        """
        done = 0
        while self._inflight and all(
                getattr(a, "is_ready", lambda: True)()
                for a in self._inflight[0].out):
            done += self._complete(self._inflight.popleft(), "ready")
        return done

    def _complete(self, rec: _Inflight, why: str) -> int:
        """Extract one in-flight batch and deliver its results.

        Blocks until the device output is ready, copies it to the host,
        runs the host-side extraction (per-unique np.unique, fan-out to
        duplicates), stamps done-times, fills the answer cache (only when
        the serving epoch still matches the dispatch epoch — a migration
        mid-flight makes the answers stale before they ever land), and
        bumps the served/executed/deduped counters and the extraction
        and table-fill counters. `why` says what made the caller wait:
        "ready" (`_retire` found it done), "backpressure" (`max_inflight`
        exceeded in `_flush`) or "drain". Returns the delivered count.
        """
        import jax

        tele = self.telemetry
        lane = f"bucket{rec.bi}"
        b_lab = str(rec.bi)
        n_exec = len(rec.unique)
        with tele.span("retire", lane, n=len(rec.tickets), epoch=rec.epoch):
            with tele.span("wait", lane, why=why):
                jax.block_until_ready(rec.out)
            nbytes = sum(int(a.nbytes) for a in rec.out)
            with tele.span("fetch", lane, bytes=nbytes):
                table, tmask, overflow = fetch_outputs(*rec.out)
            with tele.span("extract", lane, n=n_exec):
                if rec.inverse is None:
                    extracted = extract_batch(rec.bucket, rec.unique, table,
                                              tmask, overflow)
                else:
                    extracted = extract_fanout(rec.bucket, rec.unique,
                                               rec.inverse, table, tmask,
                                               overflow)
            # the (batch, shard, table cap) mask of the executed rows: its
            # live rows against the rows the engine carried for them
            executed = tmask[:n_exec]
            tele.count("d2h_bytes", nbytes, bucket=b_lab)
            tele.count("table_rows_live", int(np.count_nonzero(executed)),
                       bucket=b_lab)
            tele.count("table_rows_cap", executed.size, bucket=b_lab)
            with tele.span("deliver", lane, n=len(rec.tickets)):
                self._deliver(rec, extracted)
        return len(rec.tickets)

    def _deliver(self, rec: _Inflight, extracted: list) -> None:
        """Hand each ticket of `rec` its extracted answer: done stamps,
        counters, latency histogram, ticket spans and answer-cache fill."""
        tele = self.telemetry
        tr = tele.trace
        now = self.pipeline.clock()
        fill = (self.answer_cache_cap > 0 and not self._cache_bypass
                and rec.epoch == self._state.epoch)
        b_lab = str(rec.bi)
        tele.count("executed", len(rec.unique), bucket=b_lab)
        if len(rec.tickets) > len(rec.unique):
            tele.count("deduped", len(rec.tickets) - len(rec.unique),
                       bucket=b_lab)
        for t, res in zip(rec.tickets, extracted):
            t.result = res
            t.t_done = now
            t.done = True
            tele.count("served", template=t.name)
            if rec.degraded:
                tele.count("degraded_served", template=t.name)
            tele.observe("request_latency_ms",
                         (t.t_done - t.t_enqueue) * 1e3)
            if tr.enabled:
                span = f"ticket/{t.name}"
                tr.async_begin(span, t.seq, ts=t.t_enqueue,
                               args={"flush": t.flush_reason,
                                     "epoch": t.epoch})
                tr.async_end(span, t.seq, ts=t.t_done)
            self._latencies.append((rec.bi, t.t_enqueue, t.t_flush,
                                    t.t_dispatch, t.t_done))
            if fill:
                key = (t.name, canonical_params(t.params,
                                                rec.bucket.n_params))
                if key not in self._answers:
                    self._answers[key] = res
                    if len(self._answers) > self.answer_cache_cap:
                        self._answers.popitem(last=False)

    # ---- serving -------------------------------------------------------

    def serve(self, requests: list[tuple[str, np.ndarray | None]],
              block: bool = True):
        """Execute one batch of requests; results align with request order.

        A thin synchronous wrapper over the pipeline: every request is
        submitted (without intermediate flushes) and one drain() delivers
        them — each bucket appearing in the batch dispatches exactly once,
        identical instances collapse (dedup), and each result is
        (solutions, count, overflow); bit-identical to pre-pipeline
        synchronous serving. `block` is kept for signature compatibility
        (delivery always blocks on extraction). With adaptivity on, the
        batch feeds the workload tracker and a drift check (and possibly a
        migration) runs after the batch completes. Raises KeyError /
        ValueError per submit().
        """
        del block     # extraction always blocks; kept for call-site compat
        tickets = [self.submit(name, pv, _pump=False)
                   for name, pv in requests]
        self.drain()
        if self.adaptive is not None and self._track:
            self.adaptive.maybe_adapt()
        return [t.result for t in tickets]

    def _engine(self, bucket):
        """The compiled engine for `bucket` under this server's options.

        Publishes the EngineCache's LRU eviction delta (the cache may be
        shared across servers, so each server counts only what it saw
        grow)."""
        fn = self.cache.get(bucket.signature, join_impl=self.join_impl,
                            max_per_row=self.max_per_row,
                            gather_cap=self.gather_cap, mesh=self.mesh,
                            backend=self.backend,
                            kernel_blocks=self.kernel_blocks)
        ev = self.cache.evictions
        if ev > self._evictions_seen:
            self.telemetry.count("engine_cache_evictions",
                                 ev - self._evictions_seen)
            self._evictions_seen = ev
        return fn

    @contextmanager
    def tracking_paused(self):
        """Serve without feeding the workload tracker or running drift
        checks (warmup, steady-state timing)."""
        track, self._track = self._track, False
        try:
            yield self
        finally:
            self._track = track

    def warmup(self, requests) -> None:
        """Compile every bucket the request stream touches. Warmup requests
        do not feed the workload tracker — replaying the stream to compile
        shapes must not look like served traffic — and bypass the answer
        cache entirely (no reads, no fills: a pre-warmed cache would make
        steady-state measurements all-hit)."""
        bypass, self._cache_bypass = self._cache_bypass, True
        try:
            with self.tracking_paused():
                self.serve(requests)
        finally:
            self._cache_bypass = bypass

    def reset_stats(self) -> None:
        """Zero every stats counter (and histogram), drop the recorded
        latencies, and clear the trace buffer — the steady-state
        measurement boundary after warmup. State gauges (epoch, cut
        collectives) persist: they describe the current
        serving state, not accumulated traffic."""
        self.telemetry.reset_counters()
        self.telemetry.trace.clear()
        self._latencies.clear()


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache lives
    in ``.jax_cache`` at the root of this checkout — a fixed path, since
    the path is part of every entry's key and a moving directory never
    hits."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(Path(__file__).resolve().parents[3] / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_dataset(dataset: str, scale: float, seed: int = 0):
    """(store, template queries) for "lubm" or "bsbm" at `scale`."""
    if dataset == "lubm":
        return generate_lubm(1, scale=scale, seed=seed), lubm_queries()
    return generate_bsbm(int(1000 * scale), seed=seed), bsbm_queries()


def build_partition(method: str, store, queries, n_shards: int,
                    query_weights: dict[str, float] | None = None):
    """Partition `store` by method: "wawpart" | "random" | "centralized"."""
    if method == "wawpart":
        return wawpart_partition(store, queries, n_shards=n_shards,
                                 query_weights=query_weights)
    if method == "random":
        return random_partition(store, queries, n_shards=n_shards, seed=0)
    return centralized_partition(store, queries)


def request_stream(queries, n_requests: int, *,
                   weights: dict[str, float] | None = None,
                   seed: int | np.random.SeedSequence = 0,
                   ) -> list[tuple[str, np.ndarray | None]]:
    """Request stream over the workload's template queries.

    weights=None keeps the historical deterministic round-robin. With
    weights ({template name: relative frequency}), requests are sampled
    i.i.d. from the normalized distribution using the explicit seed (an
    int or a spawned SeedSequence) — the realistic skewed traffic the
    adaptive subsystem exists for. Raises ValueError when the weights give
    zero total mass over the workload.
    """
    if weights is None:
        return [(queries[i % len(queries)].name, None)
                for i in range(n_requests)]
    names = [q.name for q in queries]
    p = np.asarray([max(0.0, float(weights.get(n, 0.0))) for n in names])
    if p.sum() <= 0:
        raise ValueError("weights give zero total mass over the workload")
    rng = np.random.default_rng(seed)
    idx = rng.choice(len(names), size=n_requests, p=p / p.sum())
    return [(names[int(i)], None) for i in idx]


def drifting_stream(queries, phases: list[tuple[int, dict[str, float]]], *,
                    seed: int = 0) -> list[tuple[str, np.ndarray | None]]:
    """Concatenated weighted phases: [(n_requests, weights), ...] — the
    template mix shifts at each phase boundary. Per-phase seeds are spawned
    from one SeedSequence: `seed + k` would make phase k of seed s collide
    with phase k-1 of seed s+1, so "independent" streams shared samples."""
    out: list[tuple[str, np.ndarray | None]] = []
    children = np.random.SeedSequence(seed).spawn(len(phases))
    for (n, w), child in zip(phases, children):
        out.extend(request_stream(queries, n, weights=w, seed=child))
    return out


def two_phase_weights(queries) -> tuple[dict[str, float], dict[str, float]]:
    """A canonical drifting mix: phase A concentrates on the first half of
    the workload's templates, phase B on the second half (with a small
    residual mass everywhere, so both phases exercise all buckets)."""
    names = [q.name for q in queries]
    half = max(1, len(names) // 2)
    a = {n: (8.0 if i < half else 0.5) for i, n in enumerate(names)}
    b = {n: (0.5 if i < half else 8.0) for i, n in enumerate(names)}
    return a, b


def replay_paced(server: WorkloadServer, stream, arrival_s: float,
                 ) -> tuple[float, list[Ticket]]:
    """Feed `stream` through the pipeline at one request per `arrival_s`.

    The open-loop load generator the latency bench and --pipeline share:
    arrivals are paced on the server's pipeline clock (the offered load
    is fixed, not adapted to service speed) — the same injectable
    timebase the tickets, latency stats, and trace spans use — the
    server is pumped while waiting so deadline flushes and in-flight
    retirement happen on time, and a final drain() delivers everything.
    Returns (elapsed seconds, tickets).
    """
    clock = server.pipeline.clock
    tickets: list[Ticket] = []
    t0 = clock()
    t_next = t0
    for name, pv in stream:
        while True:
            now = clock()
            if now >= t_next:
                break
            server.pump()
            time.sleep(min(2e-4, t_next - now))
        tickets.append(server.submit(name, pv))
        t_next += arrival_s
    server.drain()
    return clock() - t0, tickets


def main() -> None:
    """CLI entry point: partition, warm up, and serve the request stream."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=("lubm", "bsbm"), default="lubm")
    ap.add_argument("--scale", type=float, default=0.3)
    ap.add_argument("--n-shards", type=int, default=3)
    ap.add_argument("--method", choices=("wawpart", "random", "centralized"),
                    default="wawpart")
    ap.add_argument("--join", choices=("expand", "sorted"), default="sorted")
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp",
                    help="engine execution backend: dense XLA ops (jnp) or "
                         "the fused kg_scan/kg_join Pallas kernels (pallas; "
                         "native on TPU, interpret mode elsewhere — results "
                         "are bit-identical either way)")
    ap.add_argument("--batch", type=int, default=64,
                    help="requests per serve() call (and the pipeline's "
                         "full-flush threshold under --pipeline)")
    ap.add_argument("--requests", type=int, default=256,
                    help="total requests in the stream")
    ap.add_argument("--max-per-row", type=int, default=0,
                    help="ceiling on the merge-join window (0 = auto: "
                         "per-step data-sized fan-out caps; lowering it "
                         "saves compute but can trip the overflow flag)")
    ap.add_argument("--sharded", action="store_true",
                    help="serve through shard_map on a real mesh (one device "
                         "per shard) instead of the vmap simulation")
    ap.add_argument("--no-dedup", action="store_true",
                    help="disable scan-dedup of identical batch requests")
    ap.add_argument("--no-cache", action="store_true",
                    help="disable the epoch-versioned answer cache")
    ap.add_argument("--pipeline", action="store_true",
                    help="serve through the continuous-batching pipeline "
                         "(submit/pump/drain) with paced arrivals and "
                         "deadline-based partial-bucket flushes, reporting "
                         "p50/p95/p99 latency instead of batch throughput")
    ap.add_argument("--deadline-ms", type=float, default=25.0,
                    help="per-request latency budget under --pipeline: a "
                         "partial bucket dispatches when its oldest request "
                         "has waited this long (0 = fill-only batching, no "
                         "deadline flushes)")
    ap.add_argument("--arrival-ms", type=float, default=1.0,
                    help="inter-arrival gap of the paced open-loop stream "
                         "under --pipeline")
    ap.add_argument("--replicate", action="store_true",
                    help="after warmup, replicate the hottest safe cut "
                         "features onto their queries' primary shards "
                         "(removes those cross-shard gathers)")
    ap.add_argument("--adaptive", action="store_true",
                    help="track the live workload, detect drift, and migrate "
                         "shards under a budget between batches")
    ap.add_argument("--drift", action="store_true",
                    help="serve a two-phase stream whose template mix shifts "
                         "halfway (instead of round-robin)")
    ap.add_argument("--seed", type=int, default=0,
                    help="stream sampling seed (weighted/drifting streams)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the request lifecycle and write a "
                         "Chrome-trace-event JSON file after serving "
                         "(open at https://ui.perfetto.dev — see "
                         "docs/observability.md)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics snapshot after serving: "
                         "Prometheus text exposition when PATH ends in "
                         ".prom, JSON otherwise")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the serving loop in jax.profiler.trace(DIR) "
                         "for an XLA-level profile (TensorBoard/Perfetto) "
                         "alongside the app-level --trace-out")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="arm seeded deterministic fault injection, e.g. "
                         "'dispatch=0.1/4,down=1@0.2:0.6,seed=7' (see "
                         "repro.faults.FaultPlan.parse); transient-failure "
                         "retries are on by default under chaos")
    ap.add_argument("--no-retry", action="store_true",
                    help="disable the RetryPolicy under --chaos: a failed "
                         "dispatch sheds its tickets with typed errors on "
                         "the first attempt (the goodput baseline "
                         "bench_chaos compares against)")
    ap.add_argument("--grace-ms", type=float, default=2000.0,
                    help="graceful-shutdown budget on Ctrl-C: queued "
                         "requests get this long to drain before being "
                         "shed with a typed ShutdownError; --trace-out/"
                         "--metrics-out artifacts are still written")
    args = ap.parse_args()
    if args.batch < 1:
        ap.error("--batch must be >= 1")
    use_compile_cache()

    mesh = None
    if args.sharded:
        import jax

        from repro.launch.mesh import make_engine_mesh
        if len(jax.devices()) < args.n_shards:
            ap.error(f"--sharded needs >= {args.n_shards} devices, have "
                     f"{len(jax.devices())}; on CPU set XLA_FLAGS="
                     f"--xla_force_host_platform_device_count={args.n_shards}")
        mesh = make_engine_mesh(args.n_shards)

    store, queries = build_dataset(args.dataset, args.scale)

    if args.drift:
        wa, wb = two_phase_weights(queries)
        half = args.requests // 2
        stream = drifting_stream(
            queries, [(half, wa), (args.requests - half, wb)],
            seed=args.seed)
        phase_a_weights = wa
    else:
        stream = request_stream(queries, args.requests)
        phase_a_weights = None

    pipeline_cfg = PipelineConfig(
        deadline_ms=args.deadline_ms if args.deadline_ms > 0 else None,
        max_batch=args.batch)
    clock = pipeline_cfg.clock   # one timebase: partition timing, serving
    #                              timing, tickets, and trace spans agree
    t0 = clock()
    part = build_partition(args.method, store, queries, args.n_shards,
                           query_weights=phase_a_weights)
    t_part = clock() - t0
    adaptive = None
    if args.adaptive:
        from repro.adaptive.controller import AdaptiveConfig
        adaptive = AdaptiveConfig(window=max(64, args.batch * 4),
                                  check_every=args.batch,
                                  min_requests=min(64, args.batch))
    telemetry = Telemetry(trace=args.trace_out is not None,
                          annotate=args.profile is not None)
    fault_plan = FaultPlan.parse(args.chaos) if args.chaos else None
    retry = RetryPolicy() if (fault_plan is not None
                              and not args.no_retry) else None
    server = WorkloadServer(queries, part, join_impl=args.join,
                            max_per_row=args.max_per_row or None,
                            mesh=mesh, dedup=not args.no_dedup,
                            adaptive=adaptive, backend=args.backend,
                            answer_cache=not args.no_cache,
                            pipeline=pipeline_cfg, telemetry=telemetry,
                            faults=fault_plan, retry=retry)
    print(f"{args.dataset}: {len(store):,} triples -> {part.n_shards} shards "
          f"{part.shard_sizes.tolist()} ({t_part:.1f}s partitioning), "
          f"{len(queries)} template queries in {server.n_buckets} buckets"
          + (f", shard_map on mesh {dict(mesh.shape)}" if mesh is not None
             else "")
          + (f", backend={args.backend}" if args.backend != "jnp" else "")
          + (", adaptive" if args.adaptive else ""))
    print(f"  per-bucket collective counts (WawPart cuts): "
          f"{server.collective_counts()}")

    profile_ctx = nullcontext()
    if args.profile:
        import jax
        profile_ctx = jax.profiler.trace(args.profile)
    # warmup, the serving loop, and its report run under one try so an
    # interrupt anywhere (compiles included) still drains gracefully and
    # still emits the --trace-out/--metrics-out artifacts (the finally)
    # before the run exits non-zero
    try:
        # warm every (bucket, padded batch size) shape the stream will
        # produce — serving throughput below is steady-state, compile-free
        # (an adaptive migration recompiles only changed bucket signatures,
        # mid-stream)
        for i in range(0, len(stream), args.batch):
            server.warmup(stream[i:i + args.batch])
        if args.pipeline:
            # deadline flushes cut partial batches: warm the small power-
            # of-two batch shapes too, so a mid-stream flush never pays a
            # compile
            for n in (1, 2, 4, 8, 16, 32):
                if n <= args.batch:
                    server.warmup(stream[:n])

        if args.replicate:
            rep = server.replicate_hot()
            print(f"  replicated {rep['replicated_units']} unit copies "
                  f"({rep['replicated_triples']} triples), rewrote "
                  f"{rep['plans_rewritten']} plans; collectives "
                  f"{rep['collectives_before']} -> "
                  f"{rep['collectives_after']}")
            for i in range(0, len(stream), args.batch):
                server.warmup(stream[i:i + args.batch])

        server.reset_stats()
        with profile_ctx:
            if args.pipeline:
                dt, tickets = replay_paced(server, stream,
                                           args.arrival_ms / 1e3)
                answered = [t for t in tickets if t.error is None]
                n_solutions = sum(t.result[1] for t in answered)
                overflows = sum(bool(t.result[2]) for t in answered)
                served = len(tickets)
                shed = served - len(answered)
            else:
                t0 = clock()
                served = 0
                shed = 0
                n_solutions = 0
                overflows = 0
                while served < len(stream):
                    chunk = stream[served:served + args.batch]
                    for res in server.serve(chunk):
                        if res is None:     # shed with a typed error
                            shed += 1
                            continue
                        n_solutions += res[1]
                        overflows += bool(res[2])
                    served += len(chunk)
                dt = clock() - t0

        print(f"served {served} requests in {dt*1e3:.1f} ms  "
              f"({served/dt:,.0f} queries/sec, batch={args.batch})")
        st = server.stats
        per_epoch = "" if server.epoch \
            else f" (<= {server.n_buckets} buckets)"
        print(f"  solutions={n_solutions:,}  overflows={overflows}  "
              f"compiled engines={server.n_compiles}{per_epoch}  "
              f"dedup: {st['executed']}/{st['served']} instances executed")
        if args.pipeline:
            ls = server.latency_stats()
            print(f"  latency: p50={ls['p50_ms']:.1f} p95={ls['p95_ms']:.1f} "
                  f"p99={ls['p99_ms']:.1f} mean={ls['mean_ms']:.1f} ms "
                  f"(arrival={args.arrival_ms}ms, deadline="
                  f"{args.deadline_ms or 'fill-only'}ms)")
            print(f"  flushes: full={st['flush_full']} "
                  f"deadline={st['flush_deadline']} "
                  f"drain={st['flush_drain']}  "
                  f"queue_depth={server.queue_depth()} "
                  f"inflight={server.n_inflight}")
        if server.faults is not None and server.faults.enabled:
            inj = server.faults.injected
            print(f"  chaos: injected dispatch_failures={inj['dispatch']} "
                  f"shard_down={inj['shard_down']}; recovered "
                  f"retries={st['retries']} shed={st['shed']} "
                  f"timeouts={st['timeouts']} "
                  f"degraded_served={st['degraded_served']}")
        if st["cache_hits"] or st["cache_misses"]:
            total = st["cache_hits"] + st["cache_misses"]
            print(f"  answer cache: {st['cache_hits']}/{total} hits "
                  f"({st['cache_hits']/max(1, total):.0%})")
        if server.adaptive is not None:
            print(f"  adaptive: epoch={server.epoch}, "
                  f"{server.adaptive.n_migrations} migrations")
            for ev in server.adaptive.events:
                mig = ev.migration or {}
                print(f"    [{ev.severity}] divergence={ev.divergence:.3f} "
                      f"mode={ev.mode} moved={ev.moved_triples}"
                      f"/{ev.budget_triples} budget, "
                      f"cost {ev.cost_before:.0f}->{ev.cost_after:.0f}"
                      + (f", rewrote {mig['plans_rewritten']} plans, "
                         f"reused {mig['signatures_reused']} engine sigs"
                         if mig else ""))
        if shed and fault_plan is None:
            raise SystemExit(f"{shed} requests shed without --chaos")
    except KeyboardInterrupt:
        out = server.shutdown(args.grace_ms / 1e3)
        st = server.stats
        print(f"\ninterrupted: drained {out['drained']} and shed "
              f"{out['shed']} queued requests within the "
              f"{args.grace_ms:g} ms grace budget; "
              f"served={st['served']} total")
        raise
    finally:
        if args.trace_out:
            telemetry.dump_trace(args.trace_out)
            print(f"  trace: {len(telemetry.trace)} events "
                  f"({telemetry.trace.dropped} dropped) -> {args.trace_out}")
        if args.metrics_out:
            telemetry.dump_metrics(args.metrics_out)
            print(f"  metrics snapshot -> {args.metrics_out}")
        if args.profile:
            print(f"  jax profiler trace -> {args.profile}")


if __name__ == "__main__":
    main()
