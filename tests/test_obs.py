"""Observability subsystem (ISSUE-8): trace, metrics, telemetry wiring.

The invariants this file owns:
  * the trace recorder exports well-formed Chrome trace-event JSON, and a
    FakeClock-driven pipelined serve produces a matched async begin/end
    ticket span pair per request plus flush/stage/dispatch/retire spans
    on the bucket lanes;
  * a migration emits an instant event and an epoch bump, and tickets
    queued across the bump record the new epoch in their span args;
  * the metrics registry enforces label cardinality, snapshot/delta
    subtract counters and histograms (never gauges), and the Prometheus
    text exposition round-trips through its parser;
  * the drain-time self-check fires on a deliberately broken counter;
  * tracing disabled records zero events and stays bit-identical to the
    traced path;
  * cut_collectives gauges equal WorkloadServer.collective_counts();
  * `Telemetry.span` records the recorder's span and opens one profiler
    annotation, and with both off reads no clock; a traced request shows
    its server phases (submit, flush, stage, dispatch, retire and the
    wait/fetch/extract/deliver inside it) on its bucket lane;
  * the extraction and table-fill counters count a hand-built batch, and
    bucket programs are named by their signature.
"""
import json
from contextlib import contextmanager

import numpy as np
import pytest

from repro.core.partitioner import wawpart_partition
from repro.kg.workloads import lubm_queries
from repro.obs import (MetricError, MetricsRegistry, Telemetry,
                       TraceRecorder, parse_prometheus, snapshot_delta)
from repro.launch.serve import (Counter, PipelineConfig, WorkloadServer,
                                request_stream)


@pytest.fixture(scope="module")
def lubm_served(lubm_small):
    qs = lubm_queries()
    part = wawpart_partition(lubm_small, qs, n_shards=3)
    return qs, part


class FakeClock:
    """Deterministic monotonic clock the tests advance by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _eq(a, b):
    return (np.array_equal(a[0], b[0]) and a[1] == b[1]
            and bool(a[2]) == bool(b[2]))


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------

def test_recorder_chrome_export_shapes():
    clock = FakeClock()
    rec = TraceRecorder(clock)
    rec.async_begin("ticket/q", 7, args={"epoch": 0})
    clock.advance(0.001)
    with rec.span("flush/drain", tid="bucket0", args={"n": 2}):
        clock.advance(0.002)
    rec.instant("migration", args={"epoch": 1})
    clock.advance(0.001)
    rec.async_end("ticket/q", 7)
    ch = rec.to_chrome()
    evs = ch["traceEvents"]
    assert [e["ph"] for e in evs] == ["b", "X", "i", "e"]
    # seconds became microseconds, shifted so the trace starts at 0
    assert evs[0]["ts"] == 0.0
    assert evs[1]["ts"] == pytest.approx(1000.0)
    assert evs[1]["dur"] == pytest.approx(2000.0)
    assert evs[-1]["ts"] == pytest.approx(4000.0)
    # async pair matched by (cat, id); every event carries a pid
    assert evs[0]["id"] == evs[-1]["id"] == 7
    assert all(e["pid"] == 1 for e in evs)
    assert ch["displayTimeUnit"] == "ms"
    json.dumps(ch)   # must be JSON-serializable as-is


def test_recorder_disabled_is_noop_and_bounded():
    rec = TraceRecorder(FakeClock(), enabled=False)
    rec.async_begin("t", 1)
    rec.instant("x")
    with rec.span("s"):
        pass
    assert len(rec) == 0 and rec.dropped == 0
    # a full buffer drops instead of growing
    full = TraceRecorder(FakeClock(), max_events=2)
    for _ in range(5):
        full.instant("x")
    assert len(full) == 2 and full.dropped == 3


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_label_cardinality_enforced():
    reg = MetricsRegistry()
    c = reg.counter("hits", "h", ("template",))
    c.inc(template="q1")
    with pytest.raises(MetricError):
        c.inc()                                   # missing label
    with pytest.raises(MetricError):
        c.inc(template="q1", shard="0")           # undeclared label
    with pytest.raises(MetricError):
        c.inc(-1, template="q1")                  # counters only go up
    with pytest.raises(MetricError):
        reg.gauge("hits", "conflict")             # kind conflict
    assert c.total() == 1


def test_snapshot_delta_counters_histograms_not_gauges():
    reg = MetricsRegistry()
    reg.counter("served", labels=("t",))
    reg.gauge("depth", labels=("b",))
    reg.histogram("lat", labels=(), buckets=(1.0, 10.0))
    reg["served"].inc(3, t="a")
    reg["depth"].set(5, b="0")
    reg["lat"].observe(0.5)
    old = reg.snapshot()
    reg["served"].inc(2, t="a")
    reg["served"].inc(1, t="b")                   # new label set: from zero
    reg["depth"].set(9, b="0")
    reg["lat"].observe(20.0)
    d = snapshot_delta(reg.snapshot(), old)
    by_t = {s["labels"]["t"]: s["value"] for s in d["served"]["series"]}
    assert by_t == {"a": 2, "b": 1}
    assert d["depth"]["series"][0]["value"] == 9  # gauges pass through
    (lat,) = d["lat"]["series"]
    assert lat["count"] == 1 and lat["cumulative"] == [0, 0, 1]
    # reset zeroes counters/histograms but keeps gauge state
    reg.reset()
    assert reg.total("served") == 0
    assert reg["depth"].get(b="0") == 9


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("served", "requests answered", ("template",))
    reg.histogram("lat_ms", "latency", (), buckets=(1.0, 5.0))
    reg.gauge("epoch")
    reg["served"].inc(4, template="q1")
    reg["served"].inc(1, template='we"ird\nname')
    reg["lat_ms"].observe(0.5)
    reg["lat_ms"].observe(3.0)
    reg["lat_ms"].observe(100.0)
    reg["epoch"].set(2)
    text = reg.to_prometheus()
    assert "# TYPE served counter" in text
    assert "# HELP served requests answered" in text
    parsed = parse_prometheus(text)
    assert ({"template": "q1"}, 4.0) in parsed["served"]
    assert ({"template": 'we"ird\nname'}, 1.0) in parsed["served"]
    buckets = {s[0]["le"]: s[1] for s in parsed["lat_ms_bucket"]}
    assert buckets == {"1": 1.0, "5": 2.0, "+Inf": 3.0}
    assert parsed["lat_ms_sum"] == [({}, 103.5)]
    assert parsed["lat_ms_count"] == [({}, 3.0)]
    assert parsed["epoch"] == [({}, 2.0)]


@pytest.mark.parametrize("label", [
    "plain", 'quote" inside', "new\nline", "back\\slash",
    "back\\slash then n", r"\n",          # literal backslash + n, no newline
    "\\\n",                               # literal backslash THEN newline
    'all \\ of " them\ntogether', "trailing\\",
])
def test_prometheus_label_escaping_round_trip(label):
    """Every escapable label value survives exposition -> parse exactly.

    The adversarial cases are literal-backslash-before-n: a sequential
    unescape chain turns the escaped form of "\\n" (backslash + n) into
    a real newline; the single-pass parser must not.
    """
    reg = MetricsRegistry()
    reg.counter("served", "s", ("template",))
    reg["served"].inc(1, template=label)
    parsed = parse_prometheus(reg.to_prometheus())
    assert parsed["served"] == [({"template": label}, 1.0)]


def test_prometheus_fmt_edge_values():
    """Exposition formats ints without a trailing .0, floats via repr,
    and non-finite gauge values in a form its parser reads back."""
    reg = MetricsRegistry()
    reg.gauge("g", labels=("k",))
    reg["g"].set(3.0, k="int")            # integral float -> "3"
    reg["g"].set(-0.0, k="negzero")
    reg["g"].set(float("inf"), k="inf")
    reg["g"].set(2**63, k="big")          # large int stays exact
    reg["g"].set(0.1, k="frac")           # repr keeps full precision
    text = reg.to_prometheus()
    assert 'g{k="int"} 3\n' in text + "\n"
    assert 'g{k="big"} 9223372036854775808' in text
    assert 'g{k="frac"} 0.1' in text
    vals = {s[0]["k"]: s[1] for s in parse_prometheus(text)["g"]}
    assert vals["inf"] == float("inf")
    assert vals["negzero"] == 0.0
    assert vals["big"] == float(2**63)


def test_snapshot_delta_new_series_and_bucket_mismatch():
    """Series existing only in the new snapshot count from zero, and a
    histogram whose bucket layout changed between snapshots is treated
    as new rather than misaligned-subtracted."""
    reg = MetricsRegistry()
    reg.counter("c", labels=("t",))
    reg.histogram("h", labels=(), buckets=(1.0, 10.0))
    old = reg.snapshot()                  # empty: no series yet
    reg["c"].inc(2, t="a")
    reg["h"].observe(0.5)
    d = snapshot_delta(reg.snapshot(), old)
    assert d["c"]["series"][0]["value"] == 2
    assert d["h"]["series"][0]["count"] == 1
    # stale snapshot with a different bucket layout: counted from zero
    new = reg.snapshot()
    stale = json.loads(json.dumps(old))
    stale["h"] = {"kind": "histogram", "series": [
        {"labels": {}, "cumulative": [5], "sum": 1.0, "count": 5}]}
    d = snapshot_delta(new, stale)
    (h,) = d["h"]["series"]
    assert h["cumulative"] == new["h"]["series"][0]["cumulative"]
    assert h["count"] == new["h"]["series"][0]["count"]


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------

def test_traced_pipeline_lifecycle_and_migration(lubm_served):
    """One traced pipelined run: per-ticket async spans, bucket-lane
    flush/stage/dispatch/retire spans, a migration instant event, and
    post-migration tickets carrying the new epoch."""
    from repro.adaptive.repartition import incremental_repartition
    from repro.launch.serve import two_phase_weights

    qs, part = lubm_served
    clock = FakeClock()
    tele = Telemetry(trace=True, clock=clock)
    srv = WorkloadServer(qs, part, answer_cache=False, telemetry=tele,
                         pipeline=PipelineConfig(deadline_ms=10.0,
                                                 max_batch=64, clock=clock))
    stream = request_stream(qs, 9)
    tickets = [srv.submit(n, p, _pump=False) for n, p in stream]
    clock.advance(0.011)
    srv.pump()                                    # deadline flushes
    srv.drain()

    _wa, wb = two_phase_weights(qs)
    res = incremental_repartition(part, qs, wb, budget_frac=0.15)
    late = srv.submit(qs[0].name, _pump=False)    # queued across the bump
    srv.migrate(res.part)
    srv.drain()
    tickets.append(late)
    assert late.epoch == 1

    evs = tele.trace.to_chrome()["traceEvents"]
    begins = {e["id"] for e in evs if e["ph"] == "b"}
    ends = {e["id"] for e in evs if e["ph"] == "e"}
    assert begins == ends == {t.seq for t in tickets}
    lanes = {e["tid"] for e in evs if e["ph"] == "X"}
    span_names = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"stage", "dispatch", "retire"} <= span_names
    assert any(n.startswith("flush/") for n in span_names)
    assert any(t.startswith("bucket") for t in lanes)
    instants = [e for e in evs if e["ph"] == "i"]
    assert any(e["name"] == "migration" and e["args"]["epoch"] == 1
               for e in instants)
    # the late ticket's span records the post-migration epoch
    (late_b,) = [e for e in evs
                 if e["ph"] == "b" and e["id"] == late.seq]
    assert late_b["args"]["epoch"] == 1
    assert tele.total("epoch_bumps") == 1
    assert srv.telemetry.registry["epoch"].get() == 1.0


def test_labeled_counters_match_flat_stats(lubm_served):
    qs, part = lubm_served
    srv = WorkloadServer(qs, part,
                         pipeline=PipelineConfig(deadline_ms=None,
                                                 max_batch=8))
    stream = request_stream(qs, 20)
    srv.serve(stream)
    srv.serve(stream[:5])                         # answer-cache hits
    st = srv.stats
    tele = srv.telemetry
    assert st[Counter.SERVED] == 25 and st["served"] == 25
    assert st[Counter.CACHE_HITS] == 5
    # label sums equal the flat view for every counter
    for c in Counter:
        assert tele.total(c.value) == st[c], c
    # per-template served splits by the stream's round-robin mix
    served = {s["labels"]["template"]: s["value"]
              for s in tele.snapshot()["served"]["series"]}
    assert sum(served.values()) == 25
    assert set(served) <= {q.name for q in qs}
    # the latency histogram saw every completed request
    (lat,) = tele.snapshot()["request_latency_ms"]["series"]
    assert lat["count"] == 25
    # flush/fill observations exist per flushed bucket
    fills = tele.snapshot()["batch_fill_ratio"]["series"]
    assert fills and all(0 < s["sum"] <= s["count"] for s in fills)


def test_cut_collective_gauges_match_signatures(lubm_served):
    qs, part = lubm_served
    srv = WorkloadServer(qs, part)
    gauges = srv.telemetry.registry["cut_collectives"]
    got = [gauges.get(bucket=str(bi)) for bi in range(srv.n_buckets)]
    assert got == [float(c) for c in srv.collective_counts()]


def test_rank_site_gauges_match_the_traced_engines(lubm_served,
                                                   monkeypatch):
    """rank_sites{bucket, method} holds each dispatched bucket's rank
    searches by method, as a fresh copy of its engine counts them when
    traced; the rule is replaced by one that splits this scale's sites
    between both methods on any platform."""
    import jax

    from repro.engine import primitives
    from repro.engine.batch import EngineCache, assemble_batch, shard_perms
    monkeypatch.setattr(primitives, "rank_method", lambda n_keys, platform:
                        "compare_all" if n_keys > 1000 else "scan")
    qs, part = lubm_served
    srv = WorkloadServer(qs, part, answer_cache=False)
    srv.serve(request_stream(qs, len(qs)))
    got: dict = {}
    for s in srv.telemetry.snapshot()["rank_sites"]["series"]:
        got.setdefault(s["labels"]["bucket"], {})[s["labels"]["method"]] = \
            s["value"]
    kg, want = srv.kg, {}
    for bi, b in enumerate(srv.buckets):
        fn = EngineCache().get(b.signature, join_impl=srv.join_impl,
                               max_per_row=srv.max_per_row,
                               gather_cap=srv.gather_cap)
        pd, params = assemble_batch(b, [(0, None)])
        jax.eval_shape(fn, kg.triples, kg.valid, shard_perms(kg), pd, params)
        want[str(bi)] = {m: float(n) for m, n in fn.rank_sites.items()}
    assert got == want
    assert {m for per in got.values() for m in per} == {"scan",
                                                         "compare_all"}


def test_invariant_self_check_fires_on_broken_counter(lubm_served):
    qs, part = lubm_served
    srv = WorkloadServer(qs, part, answer_cache=False,
                         pipeline=PipelineConfig(deadline_ms=None,
                                                 max_batch=64))
    srv.serve(request_stream(qs, 4))              # healthy: drain passes
    srv.telemetry.count("served", template=qs[0].name)   # break the books
    with pytest.raises(RuntimeError, match="invariant"):
        srv.drain()


def test_tracing_disabled_zero_events_bit_identical(lubm_served):
    qs, part = lubm_served
    stream = request_stream(qs, 10)
    traced = WorkloadServer(qs, part, answer_cache=False,
                            telemetry=Telemetry(trace=True))
    want = traced.serve(stream)
    assert len(traced.telemetry.trace) > 0
    plain = WorkloadServer(qs, part, answer_cache=False, cache=traced.cache)
    got = plain.serve(stream)
    assert len(plain.telemetry.trace) == 0
    for a, b in zip(want, got):
        assert _eq(a, b)
    # the counters, extraction and table fill included, do not depend on
    # tracing
    assert plain.stats == traced.stats


# ---------------------------------------------------------------------------
# spans on both clocks
# ---------------------------------------------------------------------------

class Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`: records each name
    opened and closed."""

    def __init__(self):
        self.opened, self.closed = [], []

    def __call__(self, name):
        @contextmanager
        def ann():
            self.opened.append(name)
            yield
            self.closed.append(name)
        return ann()


@pytest.fixture
def annotations(monkeypatch):
    from repro.obs import telemetry
    ann = Annotations()
    monkeypatch.setattr(telemetry, "_jax_annotation", ann)
    return ann


@pytest.mark.parametrize("name,profiler_name", [
    ("stage", "dispatch/bucket2/stage"),
    ("dispatch", "dispatch/bucket2")])
def test_span_records_event_and_one_annotation(annotations, name,
                                               profiler_name):
    clock = FakeClock()
    clock.advance(1.0)
    tele = Telemetry(trace=True, annotate=True, clock=clock)
    with tele.span(name, "bucket2", n=3):
        assert annotations.opened == [profiler_name]
        assert annotations.closed == []
        clock.advance(0.25)
    assert annotations.opened == annotations.closed == [profiler_name]
    # the same event the recorder's own complete() records
    assert tele.trace.events == [
        {"ph": "X", "name": name, "cat": "serve", "tid": "bucket2",
         "ts": 1.0, "dur": 0.25, "args": {"n": 3}}]


def test_span_annotate_only_records_no_event(annotations):
    tele = Telemetry(annotate=True)
    with tele.span("fetch", "bucket0", bytes=8):
        pass
    assert annotations.opened == ["dispatch/bucket0/fetch"]
    assert len(tele.trace) == 0


def test_span_off_reads_no_clock_records_nothing(annotations):
    reads = []

    def clock():
        reads.append(1)
        return 0.0

    tele = Telemetry(clock=clock)
    a = tele.span("wait", "bucket0", why="ready")
    b = tele.span("deliver", "bucket1", n=2)
    assert a is b                                 # one shared null context
    with a:
        with b:
            pass
    assert reads == [] and len(tele.trace) == 0
    assert annotations.opened == []


class TickClock(FakeClock):
    """A FakeClock that also moves 1 us on every read, so nested spans
    get intervals of their own."""

    def __call__(self):
        self.t += 1e-6
        return self.t


def _spans(tele, lane):
    return [e for e in tele.trace.events
            if e["ph"] == "X" and e["tid"] == lane]


def _inside(child, parent):
    return (parent["ts"] <= child["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


def test_traced_request_phases_on_its_bucket_lane(lubm_served):
    import time

    qs, part = lubm_served
    clock = TickClock()
    tele = Telemetry(trace=True, clock=clock)
    srv = WorkloadServer(qs, part, answer_cache=False, telemetry=tele,
                         pipeline=PipelineConfig(deadline_ms=0.0,
                                                 clock=clock))
    name = qs[0].name
    lane = f"bucket{srv.route[name][0]}"
    t = srv.submit(name)                          # the nested pump flushes
    t_end = time.monotonic() + 60
    while not t.done and time.monotonic() < t_end:
        srv.pump()                                # retire once it is ready
        time.sleep(0.001)
    assert t.done and t.error is None
    ev = {e["name"]: e for e in _spans(tele, lane)}
    assert set(ev) == {"submit", "flush/deadline", "stage", "dispatch",
                       "retire", "wait", "fetch", "extract", "deliver"}
    # submit ends before the nested pump's flush begins
    assert ev["submit"]["ts"] + ev["submit"]["dur"] <= \
        ev["flush/deadline"]["ts"]
    for child in ("stage", "dispatch"):
        assert _inside(ev[child], ev["flush/deadline"])
    order = ["wait", "fetch", "extract", "deliver"]
    for a, b in zip(order, order[1:]):
        assert _inside(ev[a], ev["retire"])
        assert ev[a]["ts"] + ev[a]["dur"] <= ev[b]["ts"]
    assert _inside(ev["deliver"], ev["retire"])
    assert ev["wait"]["args"] == {"why": "ready"}
    assert ev["fetch"]["args"]["bytes"] == srv.stats["d2h_bytes"] > 0
    assert ev["extract"]["args"] == {"n": 1}
    assert ev["deliver"]["args"] == {"n": 1}
    assert ev["retire"]["args"] == {"n": 1, "epoch": 0}


def test_wait_why_backpressure_and_drain(lubm_served):
    qs, part = lubm_served
    clock = FakeClock()
    tele = Telemetry(trace=True, clock=clock)
    srv = WorkloadServer(qs, part, answer_cache=False, telemetry=tele,
                         pipeline=PipelineConfig(deadline_ms=None,
                                                 max_inflight=1,
                                                 clock=clock))
    by_bucket = {}
    for q in qs:
        by_bucket.setdefault(srv.route[q.name][0], q.name)
    (b0, n0), (b1, n1) = sorted(by_bucket.items())[:2]
    srv.submit(n0, _pump=False)
    srv.submit(n1, _pump=False)
    srv.drain()
    # the second flush exceeds max_inflight=1: the first waits for it
    (w0,) = [e for e in _spans(tele, f"bucket{b0}") if e["name"] == "wait"]
    (w1,) = [e for e in _spans(tele, f"bucket{b1}") if e["name"] == "wait"]
    assert w0["args"] == {"why": "backpressure"}
    assert w1["args"] == {"why": "drain"}


def _fake_engine(bucket, live_rows):
    """An engine stand-in returning a hand-built output for `bucket`: the
    (batch, shard, table cap) mask holds `live_rows` true rows in every
    row and shard of the batch."""
    sig = bucket.signature
    S, R, V = sig.n_shards, sig.table_cap, sig.n_vars

    def fn(tr, va, perms, pd, params):
        B = params.shape[0]
        table = np.zeros((B, S, R, V), np.int32)
        table[..., 0] = np.arange(R, dtype=np.int32)
        tmask = np.zeros((B, S, R), bool)
        tmask[..., :live_rows] = True
        return table, tmask, np.zeros((B, S), bool)
    fn.rank_sites = {}                    # no traced rank search
    return fn


def test_extraction_and_fill_counters_on_hand_built_batch(lubm_served,
                                                          monkeypatch):
    qs, part = lubm_served
    srv = WorkloadServer(qs, part, answer_cache=False, dedup=False,
                         pipeline=PipelineConfig(deadline_ms=None))
    name = qs[0].name
    bi, _ = srv.route[name]
    bucket = srv.buckets[bi]
    fn = _fake_engine(bucket, live_rows=5)
    monkeypatch.setattr(srv, "_engine", lambda b: fn)
    out = fn(None, None, None, None, np.zeros((4, 1), np.int32))
    srv.serve([(name, None)] * 3)                 # padded to 4: 1 filler
    st = srv.stats
    sig = bucket.signature
    assert st["executed"] == 3
    assert st["batch_rows_padded"] == 1
    assert st["d2h_bytes"] == sum(a.nbytes for a in out)
    assert st["table_rows_live"] == int(out[1][:3].sum()) \
        == 3 * sig.n_shards * 5
    assert st["table_rows_cap"] == 3 * sig.n_shards * sig.table_cap
    # labelled by bucket
    series = srv.telemetry.snapshot()["d2h_bytes"]["series"]
    assert series == [{"labels": {"bucket": str(bi)},
                       "value": st["d2h_bytes"]}]


def test_fill_counters_match_the_real_engine_output(lubm_served):
    qs, part = lubm_served
    srv = WorkloadServer(qs, part, answer_cache=False)
    srv.serve([(q.name, None) for q in qs])      # one row per template
    st = srv.stats
    want = {"table_rows_cap": 0, "d2h_bytes": 0, "batch_rows_padded": 0}
    for bi, b in enumerate(srv.buckets):
        n = sum(1 for q in qs if srv.route[q.name][0] == bi)
        B = 1 << max(0, n - 1).bit_length()
        S, R, V = (b.signature.n_shards, b.signature.table_cap,
                   b.signature.n_vars)
        want["table_rows_cap"] += n * S * R
        # the padded batch's (R, V) int32 tables, bool masks and flags
        want["d2h_bytes"] += B * S * (R * V * 4 + R + 1)
        want["batch_rows_padded"] += B - n
    assert {k: st[k] for k in want} == want
    assert 0 < st["table_rows_live"] <= st["table_rows_cap"]


# ---------------------------------------------------------------------------
# named bucket programs
# ---------------------------------------------------------------------------

def _lowered(srv, bi):
    from repro.engine.batch import pad_requests_pow2, stage_batch
    st = srv._state
    bucket = st.buckets[bi]
    pd, params = stage_batch(bucket, pad_requests_pow2([(0, None)]),
                             mesh=srv.mesh)
    return srv._engine(bucket).lower(st.tr, st.va, st.perms, pd, params)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bucket_programs_named_by_signature(lubm_served, backend):
    import re
    qs, part = lubm_served
    srv = WorkloadServer(qs, part, backend=backend)
    names = []
    for bi, b in enumerate(srv.buckets):
        sig = b.signature
        text = _lowered(srv, bi).as_text(debug_info=True)
        (module,) = re.findall(r"module @(\S+)", text)
        want = f"jit_kg_L{sig.n_steps}_V{sig.n_vars}_R{sig.table_cap}"
        if backend != "jnp":
            want += f"_{backend}"
        assert module == want
        names.append(module)
        # every plan step's phases carry their scope
        for i in range(sig.n_steps):
            assert f"step{i}/scan" in text and f"step{i}/join" in text
    assert len(set(names)) == len(names)          # one name per bucket


def test_sharded_bucket_program_named_by_signature(lubm_small):
    from repro.launch.mesh import make_engine_mesh
    from repro.launch.serve import build_partition
    qs = lubm_queries()
    part = build_partition("centralized", lubm_small, qs, 1)
    srv = WorkloadServer(qs, part, mesh=make_engine_mesh(1))
    sig = srv.buckets[0].signature
    text = _lowered(srv, 0).as_text()
    assert (f"module @jit_kg_L{sig.n_steps}_V{sig.n_vars}_R{sig.table_cap} "
            in text)


def test_reset_stats_clears_counters_trace_not_state_gauges(lubm_served):
    qs, part = lubm_served
    srv = WorkloadServer(qs, part, telemetry=Telemetry(trace=True))
    srv.serve(request_stream(qs, 4))
    assert srv.stats[Counter.SERVED] == 4 and len(srv.telemetry.trace) > 0
    srv.reset_stats()
    assert srv.stats[Counter.SERVED] == 0
    assert len(srv.telemetry.trace) == 0
    assert srv.latency_stats()["n"] == 0
    # state gauges survive: they describe the epoch, not traffic
    assert srv.telemetry.registry["cut_collectives"].get(bucket="0") \
        is not None
    srv.drain()                                   # invariants hold post-reset
