"""Helpers for the benchmark's CPU tests: a copy of the benchmark's files
shrunk to a size the CPU serves in seconds, and a run of one of its cells
with the chip check skipped."""
from __future__ import annotations

import json
import shutil
import time
from pathlib import Path

from kgbench import harness

# graph scale, batch and load that keep one CPU run within seconds
TINY = {
    "lubm1-vmap3": {"generator": {"scale": 0.05},
                    "server": {"pipeline": {"max_batch": 2}}},
}
TINY_TRAFFIC = {
    "lubm-zipf-open": {"rate_qps": 60},
}


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base.get(k, {}), v) if isinstance(v, dict) else v
    return out


def tiny_root(dest: Path) -> Path:
    """A root under `dest` with BENCHMARK.json and the benchmark's data
    files (configs, queries, traffic, metric readers), shrunk by TINY."""
    bench = json.loads((harness.REPO / "BENCHMARK.json").read_text())
    src = harness.REPO / bench["paths"][0]
    dst = dest / bench["paths"][0]
    for sub in ("configs", "queries", "traffic", "metrics"):
        shutil.copytree(src / sub, dst / sub)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    for c in bench["configs"]:
        path = dest / c["file"]
        path.write_text(json.dumps(_merge(json.loads(path.read_text()),
                                          TINY.get(c["name"], {}))))
    for name, over in TINY_TRAFFIC.items():
        path = dst / "traffic" / f"{name}.json"
        path.write_text(json.dumps(_merge(json.loads(path.read_text()),
                                          over)))
    return dest


def run_cpu(root: Path, cell: str, seed: int = 5, seconds: float = 2.0,
            trace: bool = False) -> dict:
    """One run of `cell` on the CPU, as run.py runs it on the chip."""
    return harness.run(harness.resolve(cell, root), seed, seconds,
                       trace=trace, t_start=time.monotonic(),
                       require_tpu=False, log=lambda *a: None)
