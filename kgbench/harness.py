"""One run of one cell: build the deployment, warm it up, measure a window
from the client's side, check every answer it compares, and reduce the
records to the cell's metrics.

Everything a cell needs is found by name, from ``BENCHMARK.json``:

- the configuration (deployment) is the JSON file the entry's ``file`` names;
- its query templates are ``<bench>/queries/<config["queries"]>.json``;
- the traffic mix is ``<bench>/traffic/<traffic>.json`` (see traffic.py);
- each per-layer metric is read by ``<bench>/metrics/<metric>.py``, a module
  with ``read(record) -> float | None``.

So a new cell is new files and new entries of ``BENCHMARK.json``. Its
configuration and traffic files each carry a ``"cpu"`` block, the sizes at
which the CPU tests run them (testkit.py); nothing here reads it. `resolve`
refuses a cell whose ``chips`` differs from its configuration's, or, under
the ``mesh`` placement, from its shard count.

The program is reached only through its public entry points:
``TripleStore.from_string_triples``, ``build_partition``,
``WorkloadServer`` with ``submit``/``pump``/``drain``/``warmup``/
``reset_stats``, and ``Telemetry(trace=True, annotate=True)`` in traced
runs.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from kgbench import reference, stats
from kgbench.generators import generate
from kgbench.traffic import Mix, Request, closed_loop, open_loop

REPO = Path(__file__).resolve().parents[1]


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    """A workload entry of BENCHMARK.json with everything it names."""
    name: str
    chips: int
    config: dict
    templates: dict              # template name -> list of (s, p, o)
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    readers: dict = field(default_factory=dict)   # metric -> read()


def load_reader(path: Path):
    """The ``read`` function of a per-layer metric's reader file."""
    spec = importlib.util.spec_from_file_location(
        "kgbench_metric_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(cell_name: str, root: Path = REPO) -> Cell:
    """The cell `cell_name` of ``root/BENCHMARK.json``, with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bdir = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    w = cells[cell_name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    check_chips(cell_name, int(w["chips"]), config)
    queries = json.loads(
        (bdir / "queries" / f"{config['queries']}.json").read_text())
    templates = {t["name"]: [tuple(p) for p in t["patterns"]]
                 for t in queries["templates"]}
    traffic = json.loads((bdir / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def here(m):
        return cell_name in m.get("workloads", [cell_name])

    per_layer = [m for m in bench["per_layer"] if here(m)]
    cell = Cell(cell_name, int(w["chips"]), config, templates, traffic,
                [m for m in bench["end_to_end"] if here(m)], per_layer)
    cell.readers = {m["name"]: load_reader(bdir / "metrics"
                                           / f"{m['name']}.py")
                    for m in per_layer}
    return cell


def check_chips(cell_name: str, chips: int, config: dict) -> None:
    """Raises ValueError where the cell's chips, its configuration's chips
    and, for the mesh placement, its shard count disagree: the shard_map
    path holds one shard on each device."""
    if chips != int(config["chips"]):
        raise ValueError(f"cell {cell_name!r} asks for {chips} chip(s); its "
                         f"configuration states {config['chips']}")
    shards = int(config["partition"]["shards"])
    if config["placement"] == "mesh" and shards != chips:
        raise ValueError(f"cell {cell_name!r}: the mesh placement needs one "
                         f"chip per shard; {shards} shards on {chips} "
                         f"chip(s)")


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

@dataclass
class Param:
    """One parameter of a template: the constant it replaces, where, and
    the value the plan is sized for."""
    constant: str
    slots: list[tuple[int, int]]     # (pattern index, position)
    domain: list[str]
    plan_value: str


def template_params(cell: Cell, graph: reference.Graph
                    ) -> dict[str, list[Param]]:
    """Each parameterized template's params, planned at the domain value
    whose parameterized patterns have the most matches in the graph
    (ties: the first name), so the plan's capacities hold the heaviest
    instance."""
    rule = cell.traffic.get("plan_value", "most_matches")
    if rule != "most_matches":
        raise ValueError(f"unknown plan_value rule {rule!r}")
    doms = domains(cell, graph)
    out: dict[str, list[Param]] = {}
    for name, specs in cell.traffic.get("params", {}).items():
        pats = cell.templates[name]
        params = []
        for spec in specs:
            slots = [(i, pos) for i, pat in enumerate(pats)
                     for pos, t in enumerate(pat) if t == spec["constant"]]
            if not slots:
                raise ValueError(f"{name}: no constant {spec['constant']!r}")
            domain = doms[spec["domain_type"]]
            total: dict[int, int] = {}
            for i, pos in slots:
                for v, n in graph.count_by(pats[i], pos).items():
                    total[v] = total.get(v, 0) + n
            weight = {t: total.get(graph.id_of(t), 0) for t in domain}
            best = min(domain, key=lambda t: (-weight[t], t))
            params.append(Param(spec["constant"], slots, domain, best))
        out[name] = params
    return out


def domains(cell: Cell, graph: reference.Graph) -> dict[str, list[str]]:
    """{domain type: its members} for every parameter of the traffic."""
    return {s["domain_type"]: graph.subjects_of_type(s["domain_type"])
            for specs in cell.traffic.get("params", {}).values()
            for s in specs}


def bind(patterns, params: list[Param], values) -> list[tuple]:
    """The template's patterns with each parameter set to its value."""
    pats = [list(p) for p in patterns]
    for prm, val in zip(params, values):
        for i, pos in prm.slots:
            pats[i][pos] = val
    return [tuple(p) for p in pats]


def to_query(name: str, patterns):
    """A template as the program's query IR."""
    from repro.kg.query import Query, TriplePattern, c, v
    term = (lambda t: v(t[1:]) if reference.is_var(t) else c(t))
    return Query(name, tuple(TriplePattern(term(s), term(p), term(o))
                             for s, p, o in patterns))


@dataclass
class Deployment:
    """The program as one cell deploys it."""
    server: object
    store: object
    params: dict[str, list[Param]]
    graph: reference.Graph


def build(cell: Cell, seed: int, *, telemetry=None, graph=None,
          cache=None) -> Deployment:
    """Data, partition and server for `cell`; compiles nothing.

    WawPart partitions the graph for the templates with each parameter a
    variable, the workload as it runs; the server plans each template at
    its parameters' planning values. The graph is the configuration's
    generator output, the published dataset; `seed` orders its triples,
    and with them the ids the program's dictionary gives every term, so
    each seed serves its own encoding of the same graph (every shape the
    program compiles stays the same).
    """
    from repro.kg.triples import TripleStore
    from repro.launch.serve import (PipelineConfig, WorkloadServer,
                                    build_partition)
    cfg = cell.config
    striples = generate(cfg["generator"])
    graph = graph if graph is not None else reference.Graph(striples)
    order = np.random.default_rng(
        np.random.SeedSequence(int(seed), spawn_key=(1,))).permutation(
        len(striples))
    store = TripleStore.from_string_triples([striples[i] for i in order])
    params = template_params(cell, graph)
    queries, workload, spec = [], [], {}
    for name, pats in cell.templates.items():
        prm = params.get(name, [])
        queries.append(to_query(name, bind(pats, prm,
                                           [p.plan_value for p in prm])))
        # the partitioner sees each parameter as what it is, a variable
        workload.append(to_query(name, bind(
            pats, prm, [f"?_param{k}" for k in range(len(prm))])))
        if prm:
            spec[name] = {slot: k for k, p in enumerate(prm)
                          for slot in p.slots}
    part = build_partition(cfg["partition"]["method"], store, workload,
                           int(cfg["partition"]["shards"]))
    mesh = None
    if cfg["placement"] == "mesh":
        from repro.launch.mesh import make_engine_mesh
        mesh = make_engine_mesh(int(cfg["partition"]["shards"]))
    kw = dict(cfg.get("server", {}))
    if "pipeline" in kw:
        kw["pipeline"] = PipelineConfig(**kw["pipeline"])
    if telemetry is not None:
        kw["telemetry"] = telemetry
    if cache is not None:       # engines compiled for an earlier build
        kw["cache"] = cache
    server = WorkloadServer(queries, part, params_spec=spec, mesh=mesh, **kw)
    return Deployment(server, store, params, graph)


def param_vector(dep: Deployment, template: str, values) -> np.ndarray | None:
    """The request's parameter ids in the program's encoding."""
    if not values:
        return None
    d = dep.store.dictionary
    return np.asarray([d.id_of(v) for v in values], np.int32)


def warm_shapes(dep: Deployment) -> list[tuple[int, int, list]]:
    """(bucket, padded batch, requests) for every shape the cell's traffic
    can reach: each bucket at each power of two up to the pipeline's
    max_batch, or up to the bucket's number of distinct instances where
    dedup keeps every dispatch below that."""
    srv = dep.server
    max_batch = srv.pipeline.max_batch
    out = []
    for bi, bucket in enumerate(srv.buckets):
        names = [p.query.name for p in bucket.plans]
        pools = [_instances(dep, n, max_batch) for n in names]
        distinct = sum(len(p) for p in pools)
        top = min(max_batch, 1 << max(0, distinct - 1).bit_length())
        pool = [x for group in zip(*[p + [None] * (max_batch - len(p))
                                     for p in pools])
                for x in group if x is not None]
        b = 1
        while b <= top:
            out.append((bi, b, pool[:b]))
            b *= 2
    return out


def warmup(dep: Deployment) -> list[tuple[int, int]]:
    """Run every shape of `warm_shapes` once; returns (bucket, batch)."""
    shapes = warm_shapes(dep)
    for _, _, reqs in shapes:
        dep.server.warmup(reqs)
    return [(bi, b) for bi, b, _ in shapes]


def _instances(dep: Deployment, name: str, limit: int) -> list:
    """Up to `limit` distinct requests of one template."""
    prm = dep.params.get(name, [])
    if not prm:
        return [(name, None)]
    out = []
    sizes = [len(p.domain) for p in prm]
    for j in range(min(limit, int(np.prod(sizes)))):
        vals, k = [], j
        for p, n in zip(prm, sizes):
            vals.append(p.domain[k % n])
            k //= n
        out.append((name, param_vector(dep, name, vals)))
    return out


# ---------------------------------------------------------------------------
# compilations, as JAX reports them
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts programs traced or compiled (or loaded from the persistent
    cache) in this process, as JAX's monitoring events report them."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.counts = {e: 0 for e in self.EVENTS}
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event in self.counts:
                self.counts[event] += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def close(self) -> None:
        """Stop counting."""
        import jax.monitoring
        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)

    def total(self) -> int:
        """Programs traced plus programs compiled, so far."""
        return sum(self.counts.values())


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    when set, else inside the checkout), keeping every program however
    quickly it compiled, so that a warm set-up loads them all."""
    import jax
    from repro.launch.serve import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int, *, require_tpu: bool) -> dict:
    """The devices as JAX reports them; raises NoAccelerator without a
    TPU holding `chips` chips when `require_tpu`."""
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(f"the cell needs {chips} TPU chip(s); JAX sees "
                            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips, as the
    runtime reports it (0 where it reports nothing)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

@dataclass
class Check:
    """What the comparison with the reference found."""
    compared: int = 0
    mismatched: int = 0
    overflowed: int = 0
    unanswered: int = 0
    bad: set = field(default_factory=set)     # ids of failed requests

    def lines(self) -> dict:
        """Each number compared with its limit (all exact: limit 0)."""
        return {"mismatched": {"value": self.mismatched, "limit": 0},
                "overflowed": {"value": self.overflowed, "limit": 0},
                "unanswered": {"value": self.unanswered, "limit": 0},
                "compared": {"value": self.compared, "limit": None}}

    @property
    def correct(self) -> bool:
        """Every compared answer equals the reference's, none overflowed,
        and every request got an answer."""
        return (self.mismatched == 0 and self.overflowed == 0
                and self.unanswered == 0 and self.compared > 0)


def reference_rows(dep: Deployment, cell: Cell, template: str, values,
                   answer=None) -> np.ndarray | None:
    """The reference's solutions in the program's term ids, sorted and
    distinct as the program returns them; None when a solution holds a
    term the program's dictionary lacks. `answer` (a function of the
    reference's rows) stands in for the reference's evaluation in a
    control run."""
    pats = bind(cell.templates[template], dep.params.get(template, []),
                values)
    rows = dep.graph.evaluate(pats)
    if answer is not None:
        rows = answer(rows)
    terms = dep.graph.decode(rows)
    d = dep.store.dictionary
    try:
        ids = np.asarray([[d.id_of(t) for t in row] for row in terms],
                         np.int32).reshape(rows.shape)
    except KeyError:
        return None
    return np.unique(ids, axis=0) if len(ids) else ids


def check(dep: Deployment, cell: Cell, requests: list[Request],
          compare: list[Request], answer=None) -> Check:
    """Flags of every request, answers of `compare` against the reference.

    A request that never got an answer, or got a typed error, is
    unanswered; one whose answer carries the overflow flag overflowed;
    a compared answer that differs from the reference's mismatched."""
    out = Check()
    for r in requests:
        t = r.ticket
        if t is None or not t.done or t.error is not None or t.result is None:
            out.unanswered += 1
            out.bad.add(id(r))
        elif t.result[2]:
            out.overflowed += 1
            out.bad.add(id(r))
    cache: dict = {}
    for r in compare:
        if id(r) in out.bad:
            continue
        key = (r.template, r.values)
        if key not in cache:
            cache[key] = reference_rows(dep, cell, r.template, r.values,
                                        answer)
        want = cache[key]
        rows, n, _ = r.ticket.result
        out.compared += 1
        if want is None or n != len(want) or rows.shape != want.shape \
                or not np.array_equal(rows, want):
            out.mismatched += 1
            out.bad.add(id(r))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclass
class Record:
    """What a traced run recorded, for the per-layer metric readers."""
    loop: str                  # "open" or "closed"
    requests: list[Request]    # every request of the window
    counters: dict             # the server's stats after the drain
    spans: list[dict]          # the server's trace events
    device: dict | None        # tracereduce.reduce() of the window


def serve_window(dep: Deployment, mix: Mix, schedule, seconds: float, *,
                 annotate=None):
    """Drive the server from the client's side for `seconds`: the open
    loop over `schedule`, or the closed loop of `mix`. Returns (requests,
    window start, window end) on the server's clock, the clock its
    tickets are stamped with; answers still outstanding at the end are
    left to drain()."""
    srv = dep.server
    clock = srv.pipeline.clock

    def submit(r: Request):
        return srv.submit(r.template, param_vector(dep, r.template, r.values))

    t0 = clock()
    if mix.traffic["loop"] == "open":
        requests = open_loop(submit, srv.pump, schedule, t0, seconds,
                             clock=clock, annotate=annotate)
    else:
        requests = closed_loop(submit, srv.pump, mix, t0, seconds,
                               clock=clock, annotate=annotate)
    return requests, t0, clock()


def run(cell: Cell, seed: int, seconds: float, *, trace: bool,
        t_start: float, require_tpu: bool = True,
        log=lambda *a: print(*a, file=sys.stderr, flush=True)) -> dict:
    """One run of `cell`; returns the result object. `t_start` is the
    process's start on the monotonic clock.

    Raises NoAccelerator (before any work) when `require_tpu` and JAX finds
    no TPU with the cell's chips."""
    device = device_info(cell.chips, require_tpu=require_tpu)
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    try:
        return _run(cell, seed, seconds, trace, t_start, require_tpu, log,
                    device, cache_dir, counter)
    finally:
        counter.close()


def _run(cell, seed, seconds, trace, t_start, require_tpu, log, device,
         cache_dir, counter) -> dict:
    telemetry = None
    if trace:
        from repro.obs import Telemetry
        telemetry = Telemetry(trace=True, annotate=True)
    t = time.monotonic()
    dep = build(cell, seed, telemetry=telemetry)
    srv = dep.server
    log(f"build: {len(dep.store)} triples, KG block "
        f"{tuple(srv.kg.triples.shape)}, {srv.n_buckets} buckets "
        f"({time.monotonic() - t:.2f} s); compile cache {cache_dir}")
    t = time.monotonic()
    before = counter.total()
    shapes = warmup(dep)
    log(f"warm-up: {len(shapes)} shapes {shapes} "
        f"({time.monotonic() - t:.2f} s, {counter.total() - before} "
        f"programs traced or compiled, {counter.cache_hits} cache hits)")
    mix = Mix(cell.traffic, domains(cell, dep.graph), seed)
    loop = cell.traffic["loop"]
    schedule = mix.open_schedule(seconds) if loop == "open" else None
    srv.reset_stats()

    annotate = None
    profiler = nullcontext()
    if trace:
        from kgbench import tracereduce
        from jax.profiler import TraceAnnotation
        annotate = TraceAnnotation
        profiler = tracereduce.Profile()
    compiles0 = counter.total()
    with profiler:
        with annotate("kgbench/window") if annotate else nullcontext():
            requests, t0, t1 = serve_window(dep, mix, schedule, seconds,
                                            annotate=annotate)
        executed_traced = srv.stats["executed"]
        in_window = counter.total() - compiles0
        # drained before the profiler stops, so that collecting the trace
        # delays no answer; the reduction reads only the window's span
        srv.drain()
        t_end = srv.pipeline.clock()
    peak = memory_peak(cell.chips) if require_tpu else 0
    counters = srv.stats
    spans = list(srv.telemetry.trace.events) if trace else []
    log(f"window: {len(requests)} requests in {t1 - t0:.3f} s; drained "
        f"{t_end - t1:.3f} s later; {in_window} programs traced or "
        f"compiled inside the window (expected 0)")

    t = time.monotonic()
    chk = check(dep, cell, requests, requests)
    log(f"reference: {chk.compared} answers compared "
        f"({time.monotonic() - t:.2f} s)")
    origin = (lambda r: r.due) if loop == "open" else (lambda r: r.submit)
    lat = [np.inf if id(r) in chk.bad else (r.ticket.t_done - origin(r))
           * 1e3 for r in requests]
    ok_in_window = sum(1 for r in requests if id(r) not in chk.bad
                       and r.ticket.t_done <= t0 + seconds)
    values = {"latency_p50_ms": stats.percentile(lat, 50),
              "latency_p95_ms": stats.percentile(lat, 95),
              "qps": ok_in_window / seconds,
              "setup_s": t0 - t_start}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {"correct": chk.correct, "attempted": len(requests),
              "failed": len(chk.bad)}
    if not trace:
        metrics = {m["name"]: values[m["name"]] for m in cell.end_to_end}
    else:
        dev = tracereduce.reduce(profiler.events(), executed_traced)
        log(f"trace: device busy {dev['busy_s']:.3f} s of "
            f"{dev['window_s']:.3f} s; idle by host span "
            f"{dev['idle_by_label'][:5]}")
        rec = Record(loop, requests, counters, spans, dev)
        metrics = {}
        for name, read in cell.readers.items():
            v = read(rec)
            if v is not None:
                metrics[name] = v
        device["busy_s"] = dev["busy_s"]
        device["window_s"] = dev["window_s"]
        result["breakdown"] = {"device_ops": dev["ops"][:10],
                               "idle_gaps": dev["gaps"][:10]}
    result["metrics"] = {k: {"value": _num(v), "unit": units[k]}
                         for k, v in metrics.items()}
    device["memory_peak_bytes"] = peak
    result["device"] = device
    result["checks"] = chk.lines()
    for k, v in chk.lines().items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result


def _num(v: float):
    return None if v is None or not np.isfinite(v) else float(v)

