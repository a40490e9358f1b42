"""Finding a cell's files by name, and whole runs of each cell on the CPU
at a tiny size (the chip check skipped)."""
import dataclasses
import json
import shutil

import numpy as np
import pytest

from kgbench import generators, harness, testkit

BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """Tiny copies of the benchmark's files, and no persistent compile
    cache: these runs must leave the process's JAX settings alone."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "enable_compile_cache", lambda: "off")
        yield testkit.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_every_name_resolves_to_its_files(cell):
    c = harness.resolve(cell, harness.REPO)
    entry = {w["name"]: w for w in BENCH["workloads"]}[cell]
    assert c.traffic["loop"] in ("open", "closed")
    assert set(c.readers) == {m["name"] for m in BENCH["per_layer"]
                              if cell in m.get("workloads", [cell])}
    assert c.chips == entry["chips"] == c.config["chips"]
    for name in c.traffic.get("params", {}):
        assert name in c.templates


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.resolve("no-such-cell", harness.REPO)


@pytest.mark.parametrize("spec,program", [
    ({"name": "lubm", "universities": 1, "scale": 0.1, "seed": 3},
     lambda: __import__("repro.kg.generator", fromlist=["x"])
     .generate_lubm(1, scale=0.1, seed=3)),
    ({"name": "lubm", "universities": 2, "scale": 0.05, "seed": 4},
     lambda: __import__("repro.kg.generator", fromlist=["x"])
     .generate_lubm(2, scale=0.05, seed=4)),
])
def test_generator_copies_give_the_programs_triples(spec, program):
    from repro.kg.triples import TripleStore
    mine = TripleStore.from_string_triples(generators.generate(spec))
    assert np.array_equal(mine.triples, program().triples)


def in_process(c: harness.Cell) -> harness.Cell:
    """`c` built in this process, which sees one CPU device: a cell of more
    chips gets a copy of its configuration under the vmap placement. What
    these tests compare (the encoding, the evaluators, the plans) does not
    depend on where the shards live."""
    if c.chips == 1:
        return c
    return dataclasses.replace(c, config=dict(c.config, placement="vmap"))


@pytest.mark.parametrize("cell", CELLS)
def test_reference_equals_the_programs_oracle(root, cell):
    """The copied reference and the program's own host oracle agree on
    every template, at its planning values, on a tiny graph. No engine
    runs, so a multi-chip cell needs no mesh here."""
    from repro.engine.oracle import evaluate_bgp
    c = in_process(harness.resolve(cell, root))
    dep = harness.build(c, 11)
    d = dep.store.dictionary
    for name, pats in c.templates.items():
        prm = dep.params.get(name, [])
        bound = harness.bind(pats, prm, [p.plan_value for p in prm])
        want = evaluate_bgp(dep.store, harness.to_query(name, bound))
        got = dep.graph.evaluate(bound)
        got = np.asarray([[d.id_of(t) for t in row]
                          for row in dep.graph.decode(got)],
                         np.int32).reshape(got.shape)
        got = np.unique(got, axis=0) if len(got) else got
        assert np.array_equal(got, want), name


@pytest.mark.parametrize("cell", CELLS)
def test_a_tiny_run_is_correct_and_reports_every_metric(root, cell):
    res = testkit.run_cpu(root, cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in BENCH["end_to_end"]
                                   if cell in m.get("workloads", [cell])}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["mismatched"] == {"value": 0, "limit": 0}
    assert res["checks"]["compared"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_a_traced_tiny_run_reads_the_host_metrics(root):
    res = testkit.run_cpu(root, "lubm-zipf-open", trace=True)
    assert res["correct"] is True
    # the CPU has no TPU plane: device metrics stay silent, never 0
    assert "device.idle_pct" not in res["metrics"]
    assert "engine.device_ms_per_query" not in res["metrics"]
    for name in ("queue.wait_p95_ms", "queue.rows_per_dispatch",
                 "dedup.fanout", "stage.ms_per_flush"):
        assert res["metrics"][name]["value"] > 0
    assert {"device_ops", "idle_gaps"} <= set(res["breakdown"])


def test_every_answer_of_the_window_is_compared(root):
    res = testkit.run_cpu(root, "lubm-zipf-open", seed=2**31 + 3)
    assert res["correct"] is True
    assert res["checks"]["compared"]["value"] == res["attempted"] > 0


def test_a_cell_added_as_files_alone_runs(root, tmp_path):
    """A new configuration, traffic mix and metric are files and entries
    of BENCHMARK.json; no existing file changes."""
    new = tmp_path / "r"
    shutil.copytree(root, new)
    bench = json.loads((new / "BENCHMARK.json").read_text())
    bdir = new / bench["paths"][0]
    cfg = json.loads((bdir / "configs" / "lubm1-vmap3.json").read_text())
    cfg["partition"]["shards"] = 2
    (bdir / "configs" / "lubm-two.json").write_text(json.dumps(cfg))
    (bdir / "traffic" / "lubm-steady.json").write_text(json.dumps({
        "loop": "open", "rate_qps": 15,
        "mix": {"LUBM-Q6": 1, "LUBM-Q4": 1},
        "params": {"LUBM-Q4": [{"constant": "ub:U0_Dept0",
                                "domain_type": "ub:Department",
                                "dist": "uniform"}]}}))
    (bdir / "metrics" / "answers.rows_mean.py").write_text(
        "def read(rec):\n"
        "    n = [r.ticket.result[1] for r in rec.requests]\n"
        "    return sum(n) / len(n) if n else None\n")
    bench["configs"].append(dict(bench["configs"][0], name="lubm-two",
                                 file="kgbench/configs/lubm-two.json"))
    bench["workloads"].append({"name": "lubm-two.steady",
                               "config": "lubm-two",
                               "traffic": "lubm-steady", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answers.rows_mean", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "extraction",
                               "moves": "qps",
                               "workloads": ["lubm-two.steady"]})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    res = testkit.run_cpu(new, "lubm-two.steady", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["answers.rows_mean"]["value"] > 0
    assert "client.lag_p95_ms" not in res["metrics"]


def full_copy(dest):
    """The benchmark's own files, at full size, under `dest`."""
    bdir = BENCH["paths"][0]
    for sub in ("configs", "queries", "traffic", "metrics"):
        shutil.copytree(harness.REPO / bdir / sub, dest / bdir / sub)
    shutil.copy(harness.REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    return dest, dest / bdir


def test_a_mesh_cell_added_as_files_alone_runs(tmp_path):
    """A four-chip mesh configuration and a closed-loop mix, each with its
    own CPU size, are files and entries of BENCHMARK.json; the CPU tests
    run the cell on four CPU devices."""
    full, bdir = full_copy(tmp_path / "full")
    cfg = json.loads((bdir / "configs" / "lubm1-vmap3.json").read_text())
    cfg.update(chips=4, placement="mesh",
               partition=dict(cfg["partition"], shards=4))
    assert "cpu" in cfg
    (bdir / "configs" / "lubm-mesh.json").write_text(json.dumps(cfg))
    queries = json.loads((bdir / "queries" / "lubm.json").read_text())
    zipf = json.loads((bdir / "traffic" / "lubm-zipf-open.json").read_text())
    (bdir / "traffic" / "lubm-closed.json").write_text(json.dumps({
        "loop": "closed", "clients": 64,
        "sequence": [t["name"] for t in queries["templates"]],
        "params": zipf["params"], "plan_value": "most_matches",
        "cpu": {"clients": 8}}))
    bench = json.loads((full / "BENCHMARK.json").read_text())
    cell = "lubm-mesh.closed"
    bench["configs"].append(dict(bench["configs"][0], name="lubm-mesh",
                                 file="kgbench/configs/lubm-mesh.json"))
    bench["workloads"].append({"name": cell, "config": "lubm-mesh",
                               "traffic": "lubm-closed", "chips": 4,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("qps", "latency_p50_ms", "queue.rows_per_dispatch",
                         "dedup.fanout"):
            m["workloads"].append(cell)
    (full / "BENCHMARK.json").write_text(json.dumps(bench))
    root = testkit.tiny_root(tmp_path / "tiny", full)
    assert json.loads((root / "kgbench/traffic/lubm-closed.json")
                      .read_text())["clients"] == 8

    res = testkit.run_cpu(root, cell, seed=2**31 + 11)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["compared"]["value"] == res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "latency_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == 4

    res = testkit.run_cpu(root, cell, seed=12, trace=True)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["compared"]["value"] == res["attempted"] > 0
    for name in ("queue.rows_per_dispatch", "dedup.fanout"):
        assert res["metrics"][name]["value"] > 0


@pytest.mark.parametrize("kind", ["configs", "traffic"])
def test_a_file_without_a_cpu_block_is_refused(tmp_path, kind):
    """A cell whose configuration or mix carries no CPU size would run at
    full size on the CPU: tiny_root names the file instead."""
    full, bdir = full_copy(tmp_path / "full")
    w = BENCH["workloads"][0]
    name = w["config"] if kind == "configs" else w["traffic"]
    path = next((bdir / kind).glob(f"{name}.json"))
    data = json.loads(path.read_text())
    del data["cpu"]
    path.write_text(json.dumps(data))
    with pytest.raises(ValueError, match=path.name):
        testkit.tiny_root(tmp_path / "tiny", full)


@pytest.mark.parametrize("chips,config", [
    (4, {"chips": 1, "placement": "vmap", "shards": 3}),
    (1, {"chips": 4, "placement": "vmap", "shards": 3}),
    (4, {"chips": 4, "placement": "mesh", "shards": 3}),
    (2, {"chips": 2, "placement": "mesh", "shards": 4}),
])
def test_a_mis_sized_cell_is_refused(tmp_path, chips, config):
    """The cell's chips, its configuration's chips and a mesh's shards
    must agree before a run touches a device."""
    full, _ = full_copy(tmp_path)
    bench = json.loads((full / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]
    path = full / {c["name"]: c["file"]
                   for c in bench["configs"]}[cell["config"]]
    cfg = json.loads(path.read_text())
    cfg.update(chips=config["chips"], placement=config["placement"],
               partition=dict(cfg["partition"], shards=config["shards"]))
    path.write_text(json.dumps(cfg))
    cell["chips"] = chips
    (full / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match=cell["name"]):
        harness.resolve(cell["name"], full)


@pytest.mark.parametrize("cell", CELLS)
def test_the_cpu_block_changes_nothing_the_chip_run_builds(root, cell):
    """build and Mix read named keys only: a `cpu` block that would change
    the graph, the shards and the batch if it were read leaves the
    deployment and the traffic as they were."""
    from repro.launch.serve import PipelineConfig
    from kgbench.traffic import Mix
    c = in_process(harness.resolve(cell, root))
    loud = {"generator": {"scale": 0.1, "seed": 1},
            "partition": {"shards": 2},
            "server": {"pipeline": {"max_batch": 8}},
            "rate_qps": 1, "clients": 1, "params": {}}
    with_cpu = dataclasses.replace(c, config=dict(c.config, cpu=loud),
                                   traffic=dict(c.traffic, cpu=loud))
    a, b = harness.build(c, 13), harness.build(with_cpu, 13)
    assert np.array_equal(a.server.kg.triples, b.server.kg.triples)
    assert np.array_equal(a.server.kg.valid, b.server.kg.valid)
    assert a.server.n_buckets == b.server.n_buckets > 0
    for x, y in zip(a.server.buckets, b.server.buckets):
        assert x.signature == y.signature
        assert x.plans == y.plans
    assert a.params == b.params
    want = c.config.get("server", {}).get("pipeline", {}).get(
        "max_batch", PipelineConfig().max_batch)
    assert a.server.pipeline.max_batch == b.server.pipeline.max_batch == want
    doms = harness.domains(c, a.graph)
    m, n = Mix(c.traffic, doms, 17), Mix(with_cpu.traffic, doms, 17)
    if c.traffic["loop"] == "open":
        assert [(r.template, r.values, r.due) for r in m.open_schedule(5)] \
            == [(r.template, r.values, r.due) for r in n.open_schedule(5)]
    for t in c.templates:
        assert m.values(t, m.client_rng(0)) == n.values(t, n.client_rng(0))
