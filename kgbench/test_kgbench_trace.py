"""The reduction from profiler trace to busy time, op time and idle gaps:
on events made by hand, and on a trace recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from kgbench import tracereduce

SAMPLE = Path(__file__).parent / "testdata" / "trace_events.json.gz"


def test_hand_made_events():
    ms = 1_000_000
    ev = {"host": [["kgbench/window", 0, 100 * ms],
                   ["kgbench/pump", 5 * ms, 40 * ms],
                   ["dispatch/bucket2", 10 * ms, 2 * ms],
                   ["kgbench/submit", 60 * ms, 30 * ms]],
          "device": [[0, "jit_a", "fusion.1", 12 * ms, 8 * ms],
                     [0, "jit_a", "fusion.2", 15 * ms, 10 * ms],
                     [0, "jit_b", "fusion.1", 50 * ms, 5 * ms],
                     # outside the window: clipped away
                     [0, "jit_b", "fusion.1", 95 * ms, 10 * ms]]}
    r = tracereduce.reduce(ev, executed=4)
    assert r["window_s"] == pytest.approx(0.1)
    # union: [12, 25) + [50, 55) + [95, 100) = 13 + 5 + 5 ms
    assert r["busy_s"] == pytest.approx(0.023)
    ops = dict((k, v) for k, v in r["ops"])
    assert ops["jit_a:fusion.1"] == pytest.approx(0.008)
    assert ops["jit_a:fusion.2"] == pytest.approx(0.010)
    assert ops["jit_b:fusion.1"] == pytest.approx(0.010)
    # gaps: [0,12) [25,50) [55,95), longest first, each with its span
    assert [(g[0], round(g[1] * 1e3, 6)) for g in r["gaps"]] == [
        ("kgbench/submit", 40.0), ("kgbench/pump", 25.0),
        ("kgbench/pump", 12.0)]
    assert sum(v for _, v in r["idle_by_label"]) == pytest.approx(0.077)


def _union_by_brute_force(ivs, step):
    """The covered length on a grid of `step` ns: each interval marks the
    cells it touches."""
    lo = min(a for a, _ in ivs)
    cells = np.zeros((max(b for _, b in ivs) - lo) // step + 2, bool)
    for a, b in ivs:
        cells[(a - lo) // step:(b - lo - 1) // step + 1] = True
    return int(cells.sum()) * step


@pytest.fixture(scope="module")
def sample():
    with gzip.open(SAMPLE, "rt") as f:
        return json.load(f)


def test_recorded_trace_busy_is_the_union_of_ops(sample):
    r = tracereduce.reduce(sample)
    (w,) = [h for h in sample["host"] if h[0] == tracereduce.WINDOW]
    ivs = [(max(s, w[1]), min(s + d, w[1] + w[2]))
           for _, _, _, s, d in sample["device"]]
    ivs = [(a, b) for a, b in ivs if b > a]
    # on a 1 us grid the marked cells overcount by at most two cells per
    # covered run
    step = 1_000
    brute = _union_by_brute_force(ivs, step) / 1e9
    runs = len(tracereduce._union(ivs))
    assert brute - 2 * step * runs / 1e9 <= r["busy_s"] <= brute
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["window_s"] == pytest.approx(w[2] / 1e9)
    # op time by name sums to at least the busy time (ops may overlap)
    assert sum(v for _, v in r["ops"]) >= r["busy_s"] * (1 - 1e-9)
    # busy plus idle gaps tile the window
    idle = sum(v for _, v in r["gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-9)


def test_recorded_trace_gaps_are_labelled_by_covering_spans(sample):
    r = tracereduce.reduce(sample)
    names = {h[0] for h in sample["host"]}
    for label, secs in r["gaps"]:
        assert label in names or label == "no host span"
        assert secs > 0
    longest = [s for _, s in r["gaps"]]
    assert longest == sorted(longest, reverse=True)
    # the server's dispatch annotations and the client's spans are there
    assert any(n.startswith("dispatch/bucket") for n in names)
    assert {"kgbench/submit", "kgbench/pump"} <= names
