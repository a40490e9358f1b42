"""Finding a cell's files by name, and whole runs of each cell on the CPU
at a tiny size (the chip check skipped)."""
import json

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


@pytest.mark.parametrize("cell", CELLS)
def test_reference_equals_the_programs_oracle(root, cell):
    """The copied reference and the program's own host oracle agree on
    every template, at its planning values, on a tiny graph."""
    from repro.engine.oracle import evaluate_bgp
    c = harness.resolve(cell, root)
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
    import shutil
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
