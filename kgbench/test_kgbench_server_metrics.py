"""The readers of the server's own phases and counters: extraction, table
fill, the device wait and host work per ticket. On records made by hand, on
a record of a program that lacks those spans and counters, and on a traced
tiny run on the CPU."""
import json
from types import SimpleNamespace

import pytest

from kgbench import harness, testkit
from kgbench.harness import Record
from kgbench.traffic import Request

BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
NAMES = ("retire.wait_ms_per_dispatch", "extract.ms_per_dispatch",
         "extract.kb_per_query", "engine.table_fill_pct", "ticket.host_us")
READERS = {name: harness.load_reader(
    harness.REPO / BENCH["paths"][0] / "metrics" / f"{name}.py")
    for name in NAMES}
SPAN_READERS = ("retire.wait_ms_per_dispatch", "extract.ms_per_dispatch",
                "ticket.host_us")

# counters as the server reports them before any of the new ones existed
OLD_COUNTERS = {"served": 12, "executed": 8, "flush_full": 0,
                "flush_deadline": 8, "flush_drain": 0}


def _span(name, dur, **args):
    return {"ph": "X", "name": name, "cat": "serve", "tid": "bucket4",
            "ts": 0.0, "dur": dur, "args": args}


def _record(spans=(), counters=None, device=None):
    req = Request("Q", (), 0.0, 0.0,
                  SimpleNamespace(done=True, t_flush=0.1, t_done=0.5))
    return Record("open", [req], dict(counters or OLD_COUNTERS),
                  list(spans), device)


def test_readers_are_declared_for_the_cell():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NAMES:
        assert declared[name]["workloads"] == ["lubm-zipf-open"]


def test_span_readers():
    spans = [_span("wait", 0.100, why="ready"),
             _span("wait", 0.300, why="backpressure"),
             _span("fetch", 0.004, bytes=1), _span("fetch", 0.006, bytes=1),
             _span("extract", 0.001, n=1), _span("extract", 0.003, n=1),
             _span("submit", 20e-6), _span("submit", 40e-6),
             _span("submit", 60e-6), _span("deliver", 120e-6, n=3),
             _span("retire", 9.0), _span("stage", 9.0)]
    counters = dict(OLD_COUNTERS, served=3)
    rec = _record(spans, counters)
    assert READERS["retire.wait_ms_per_dispatch"](rec) == \
        pytest.approx(200.0)
    # (4 + 6 + 1 + 3) ms over 2 dispatches
    assert READERS["extract.ms_per_dispatch"](rec) == pytest.approx(7.0)
    # (20 + 40 + 60 + 120) us over 3 tickets
    assert READERS["ticket.host_us"](rec) == pytest.approx(80.0)


def test_counter_readers():
    counters = dict(OLD_COUNTERS, d2h_bytes=8 * 3 * 1024 * 10,
                    table_rows_live=300, table_rows_cap=1200,
                    batch_rows_padded=5)
    rec = _record(counters=counters)
    # 8 executed rows, 30 KB each
    assert READERS["extract.kb_per_query"](rec) == pytest.approx(30.0)
    assert READERS["engine.table_fill_pct"](rec) == pytest.approx(25.0)


def test_an_untraced_record_reads_only_the_counters():
    """Tracing off records no span, while the counters stay on: the span
    readers are silent and the counter readers still read."""
    counters = dict(OLD_COUNTERS, d2h_bytes=8 * 1024, table_rows_live=1,
                    table_rows_cap=4, batch_rows_padded=0)
    rec = _record(counters=counters)
    for name in SPAN_READERS:
        assert READERS[name](rec) is None, name
    assert READERS["extract.kb_per_query"](rec) == pytest.approx(1.0)
    assert READERS["engine.table_fill_pct"](rec) == pytest.approx(25.0)


def test_a_program_without_the_spans_and_counters_reads_nothing():
    """The parent program records neither the new spans nor the new
    counters: each reader is silent and none raises."""
    spans = [_span("stage", 0.005), _span("dispatch", 0.001),
             _span("retire", 0.2), _span("flush/deadline", 0.01)]
    for counters in (OLD_COUNTERS, dict(OLD_COUNTERS, executed=0)):
        rec = _record(spans, counters)
        for name in NAMES:
            assert READERS[name](rec) is None, name


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "enable_compile_cache", lambda: "off")
        yield testkit.tiny_root(tmp_path_factory.mktemp("bench"))


def test_a_traced_tiny_run_reads_the_server_metrics(root):
    res = testkit.run_cpu(root, "lubm-zipf-open", seed=2**32 + 7,
                          trace=True)
    assert res["correct"] is True
    for name in NAMES:
        assert res["metrics"][name]["value"] > 0, name
    assert res["metrics"]["engine.table_fill_pct"]["value"] <= 100.0
