"""Helpers for the benchmark's CPU tests: a copy of the benchmark's files
shrunk to a size the CPU serves in seconds, and a run of one of its cells
with the chip check skipped.

Each configuration and traffic file that a cell names carries its own CPU
size: a ``"cpu"`` object of overrides, merged into the file's keys (nested
objects key by key) in the copy that ``tiny_root`` writes, which keeps no
``cpu`` key. The chip run reads only named keys and never ``cpu``. So a new
cell is new files and new entries of ``BENCHMARK.json``, each file with its
``cpu`` block; a file without one is refused by name.

``run_cpu`` runs a one-chip cell in this process. A cell of more chips runs
in a child process that sees as many CPU devices as the cell asks for
(``--xla_force_host_platform_device_count``), so that a mesh cell builds
its mesh; the child prints the result as the last line of its output, as
run.py does. Run this module to make such a run by hand:

  python3 -m kgbench.testkit <root> <cell> <seed> <seconds> <trace 0|1>
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from kgbench import harness

CHILD_TIMEOUT_S = 600


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base.get(k, {}), v) if isinstance(v, dict) else v
    return out


def _shrink(path: Path) -> None:
    """Merge the file's ``cpu`` block into it and drop the block."""
    data = json.loads(path.read_text())
    if not isinstance(data.get("cpu"), dict):
        raise ValueError(f"{path.name}: no \"cpu\" block of CPU test sizes; "
                         f"a file a cell names must carry one")
    path.write_text(json.dumps(_merge(data, data.pop("cpu"))))


def tiny_root(dest: Path, src: Path = harness.REPO) -> Path:
    """A root under `dest` with the BENCHMARK.json and the benchmark's data
    files (configs, queries, traffic, metric readers) of `src`, each
    configuration and traffic mix that a cell names shrunk by its own
    ``cpu`` block. Raises ValueError naming a file without one."""
    bench = json.loads((src / "BENCHMARK.json").read_text())
    bdir = bench["paths"][0]
    for sub in ("configs", "queries", "traffic", "metrics"):
        shutil.copytree(src / bdir / sub, dest / bdir / sub)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    named = {files[w["config"]] for w in bench["workloads"]} | {
        f"{bdir}/traffic/{w['traffic']}.json" for w in bench["workloads"]}
    for rel in sorted(named):
        _shrink(dest / rel)
    return dest


def run_cpu(root: Path, cell: str, seed: int = 5, seconds: float = 2.0,
            trace: bool = False) -> dict:
    """One run of `cell` on the CPU, as run.py runs it on the chip; a cell
    of more than one chip runs in a child process with that many CPU
    devices."""
    c = harness.resolve(cell, root)
    if c.chips == 1:
        return harness.run(c, seed, seconds, trace=trace,
                           t_start=time.monotonic(), require_tpu=False,
                           log=lambda *a: None)
    path = [str(harness.REPO), str(harness.REPO / "src"),
            os.environ.get("PYTHONPATH", "")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(p for p in path if p))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={c.chips}").strip()
    out = subprocess.run(
        [sys.executable, "-m", "kgbench.testkit", str(root), cell,
         str(seed), str(seconds), str(int(trace))],
        env=env, cwd=harness.REPO, capture_output=True, text=True,
        timeout=CHILD_TIMEOUT_S)
    if out.returncode != 0:
        raise RuntimeError(f"{cell} on {c.chips} CPU devices exited "
                           f"{out.returncode}:\n{out.stderr[-4000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def main(argv: list[str]) -> int:
    root, cell, seed, seconds, trace = argv
    harness.enable_compile_cache = lambda: "off"
    c = harness.resolve(cell, Path(root))
    result = harness.run(c, int(seed), float(seconds), trace=trace == "1",
                         t_start=time.monotonic(), require_tpu=False)
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
