"""Readings for the limits of the check: the program's and the control's,
on many seeds, in one process on the chip.

  python3 kgbench/calibrate.py lubm-zipf-open 10 101 102 103 ...

Arguments: the cell, the window's seconds, then the seeds. For each seed
the cell is built with that seed (sharing the engines compiled for the
first), warmed up, and served from the client's side for a window at the
cell's own load, exactly as run.py serves it; then every answer of the window
is checked twice: the program's answers against the reference,
and the control's. Prints one JSON line per seed with both readings.

The control is the reference put in the program's place with one of the
configuration's guarantees broken, the way a smaller static capacity
would break it: every answer is cut to its first `CONTROL_CAP` solutions
and carries no overflow flag. A check that cannot tell it from the
reference could not catch a capacity cut either.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_CAP = 1024


def truncated(rows):
    """The control's answer: the reference's, cut to CONTROL_CAP rows."""
    return rows[:CONTROL_CAP]


def control_check(dep, cell, compare):
    """The same check with the control in the program's place: each
    compared request is answered with the control's rows."""
    from types import SimpleNamespace

    from kgbench import harness
    from kgbench.traffic import Request
    answers, stand_in = {}, []
    for r in compare:
        key = (r.template, r.values)
        if key not in answers:
            answers[key] = harness.reference_rows(dep, cell, r.template,
                                                  r.values, truncated)
        rows = answers[key]
        stand_in.append(Request(r.template, r.values, r.due, r.submit,
                                SimpleNamespace(done=True, error=None,
                                                result=(rows, len(rows),
                                                        False))))
    return harness.check(dep, cell, stand_in, stand_in)


def main() -> int:
    from kgbench import harness
    from kgbench.traffic import Mix

    cell = harness.resolve(sys.argv[1], ROOT)
    seconds = float(sys.argv[2])
    seeds = [int(s) for s in sys.argv[3:]]
    harness.device_info(cell.chips, require_tpu=True)
    harness.enable_compile_cache()
    engines, graph = None, None
    for seed in seeds:
        t = time.monotonic()
        dep = harness.build(cell, seed, cache=engines, graph=graph)
        engines, graph = dep.server.cache, dep.graph
        shapes = harness.warmup(dep)
        print(f"seed {seed}: warmed {len(shapes)} shapes in "
              f"{time.monotonic() - t:.2f} s", file=sys.stderr, flush=True)
        mix = Mix(cell.traffic, harness.domains(cell, dep.graph), seed)
        schedule = mix.open_schedule(seconds) \
            if cell.traffic["loop"] == "open" else None
        dep.server.reset_stats()
        reqs, _, _ = harness.serve_window(dep, mix, schedule, seconds)
        dep.server.drain()
        prog = harness.check(dep, cell, reqs, reqs)
        ctrl = control_check(dep, cell, reqs)
        print(json.dumps({
            "seed": seed, "requests": len(reqs),
            "program": {k: v["value"] for k, v in prog.lines().items()},
            "control": {k: v["value"] for k, v in ctrl.lines().items()},
            "program_correct": prog.correct,
            "control_correct": ctrl.correct,
            "seconds": time.monotonic() - t}), flush=True)
        del dep
    return 0


if __name__ == "__main__":
    sys.exit(main())
