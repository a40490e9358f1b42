"""Each per-layer metric's reader, on records made by hand."""
import json
from types import SimpleNamespace

import pytest

from kgbench import harness
from kgbench.harness import Record
from kgbench.traffic import Request

BENCH = json.loads((harness.REPO / "BENCHMARK.json").read_text())
READERS = {m["name"]: harness.load_reader(
    harness.REPO / BENCH["paths"][0] / "metrics" / f"{m['name']}.py")
    for m in BENCH["per_layer"]}


def _req(due, submit, flush, done):
    return Request("Q", (), due, submit,
                   SimpleNamespace(done=True, t_flush=flush, t_done=done))


def _record(loop="open", device=None, spans=()):
    # 20 requests: request i is due at i, sent i ms late, flushed 10 i ms
    # after it was due
    reqs = [_req(i, i + i / 1e3, i + i / 1e2, i + 0.5) for i in range(20)]
    counters = {"served": 60, "executed": 40, "flush_full": 2,
                "flush_deadline": 5, "flush_drain": 1}
    return Record(loop, reqs, counters, list(spans), device)


def test_every_metric_has_a_reader():
    assert set(READERS) == {m["name"] for m in BENCH["per_layer"]}


def test_client_lag():
    assert READERS["client.lag_p95_ms"](_record()) == pytest.approx(18.0)
    assert READERS["client.lag_p95_ms"](_record("closed")) is None


def test_queue_wait_from_due_and_from_submit():
    assert READERS["queue.wait_p95_ms"](_record()) == pytest.approx(180.0)
    # nearest rank: the 19th of 20 values; closed loop: from the submit
    # time, 9 i ms
    assert READERS["queue.wait_p95_ms"](_record("closed")) == \
        pytest.approx(162.0)


def test_counter_ratios():
    rec = _record()
    assert READERS["queue.rows_per_dispatch"](rec) == pytest.approx(5.0)
    assert READERS["dedup.fanout"](rec) == pytest.approx(1.5)
    rec.counters = dict(rec.counters, executed=0, flush_full=0,
                        flush_deadline=0, flush_drain=0)
    assert READERS["queue.rows_per_dispatch"](rec) is None
    assert READERS["dedup.fanout"](rec) is None


def test_stage_span_mean():
    spans = [{"name": "stage", "dur": 0.002}, {"name": "stage", "dur": 0.004},
             {"name": "dispatch", "dur": 1.0}]
    assert READERS["stage.ms_per_flush"](_record(spans=spans)) == \
        pytest.approx(3.0)
    assert READERS["stage.ms_per_flush"](_record()) is None


def test_device_metrics():
    dev = {"busy_s": 1.5, "window_s": 6.0, "executed": 300}
    rec = _record(device=dev)
    assert READERS["engine.device_ms_per_query"](rec) == pytest.approx(5.0)
    assert READERS["device.idle_pct"](rec) == pytest.approx(75.0)
    # a trace with no device op gives nothing, never a 0 or a 100
    for dev in (None, {"busy_s": 0.0, "window_s": 6.0, "executed": 3}):
        rec = _record(device=dev)
        assert READERS["engine.device_ms_per_query"](rec) is None
        assert READERS["device.idle_pct"](rec) is None
