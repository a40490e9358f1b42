import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every assigned (architecture x input-shape) cell — plus the
paper's own federated KG-engine plans — against the production meshes:
  single-pod 16x16 ("data","model") = 256 chips,
  multi-pod  2x16x16 ("pod","data","model") = 512 chips,
and records memory_analysis / cost_analysis / per-collective byte counts to a
JSONL file that benchmarks/roofline.py and EXPERIMENTS.md consume.

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init); smoke tests and benches never import this module
so they see the real single CPU device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multipod --out results/dryrun.jsonl
  PYTHONPATH=src python -m repro.launch.dryrun --engine   # WawPart engine rows
"""
import argparse
import json
import re
import time
import traceback

from repro.engine.batch import cost_dict


_COLL_RE = re.compile(
    r"=\s*\(?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")

_DT_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
             "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in post-SPMD HLO.

    Matches the op NAME position only (`= type[shape] opcode(`) — lines that
    merely reference a collective as an operand must not count. Async pairs
    count once via -start; -done is a pass-through.
    """
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out: dict[str, float] = {k: 0.0 for k in kinds}
    counts: dict[str, int] = {k: 0 for k in kinds}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line.strip())
        if not m:
            continue
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] += n * _DT_BYTES[dt]
        counts[kind] += 1
    return {"per_kind_bytes": out, "per_kind_count": counts,
            "total_bytes": sum(out.values())}


def run_cell(arch: str, shape: str, *, multi_pod: bool) -> dict:
    import jax
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values()))) if False else \
        len(mesh.devices.flatten())
    t0 = time.time()
    cell = build_cell(arch, shape, mesh)
    with mesh:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = cost_dict(compiled)
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    # scan bodies are counted once by HloCostAnalysis; recombine scan-free
    # component lowerings with exact trip counts (LM cells only — GNN and
    # recsys programs contain no scans)
    adjusted = None
    from repro.configs import get_arch
    if get_arch(arch).family == "lm":
        from repro.launch.components import lm_component_costs
        comp = lm_component_costs(arch, shape, mesh)
        adjusted = comp
    rec = {
        "arch": arch, "shape": shape, "kind": cell.kind,
        "mesh": "2x16x16" if multi_pod else "16x16", "n_chips": n_chips,
        "model_flops": cell.model_flops,
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
        "peak_bytes_per_device":
            (getattr(mem, "argument_size_in_bytes", 0)
             + getattr(mem, "output_size_in_bytes", 0)
             + getattr(mem, "temp_size_in_bytes", 0)),
        "collectives": coll,
        "adjusted": adjusted,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "meta": {k: v for k, v in cell.meta.items()
                 if isinstance(v, (int, float, str, bool, dict))},
    }
    return rec


def run_engine_rows(*, multi_pod: bool, n_shards: int | None = None) -> list:
    """Lower the paper's federated query plans on the production mesh: the
    triple store shards across the model axis; collective bytes per query are
    the paper's distributed-join cost, statically measured."""
    import jax
    from repro.core.partitioner import random_partition, wawpart_partition
    from repro.engine.federated import ShardedKG, lower_engine
    from repro.engine.planner import make_plan
    from repro.kg.generator import generate_lubm
    from repro.kg.workloads import lubm_queries
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_shards = n_shards or int(mesh.shape["model"])
    store = generate_lubm(1, scale=0.5, seed=0)
    queries = lubm_queries()
    rows = []
    for method, pfn in [("wawpart", wawpart_partition),
                        ("random", random_partition)]:
        part = pfn(store, queries, n_shards=n_shards)
        kg = ShardedKG.build(part)
        for q in queries:
            plan = make_plan(q, part)
            lowered = lower_engine(plan, (kg.n_shards, kg.cap), mesh,
                                   axis="model")
            compiled = lowered.compile()
            coll = collective_bytes(compiled.as_text())
            cost = cost_dict(compiled)
            rows.append({
                "arch": f"kg-engine-{method}", "shape": q.name,
                "kind": "query", "mesh": "2x16x16" if multi_pod else "16x16",
                "n_gathers": plan.n_gathers,
                "n_distributed_joins": len(plan.cut_steps),
                "flops": float(cost.get("flops", 0.0)),
                "collectives": coll,
            })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--engine", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cache-update", choices=("dus", "masked"),
                    default="masked")
    args = ap.parse_args()
    from repro.launch import cells as _cells
    _cells.CACHE_UPDATE_MODE = args.cache_update

    from repro.configs import all_cells

    records = []
    meshes = [False, True] if args.both_meshes else [args.multipod]

    def emit(rec):
        records.append(rec)
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    if args.engine:
        for mp in meshes:
            for rec in run_engine_rows(multi_pod=mp):
                emit(rec)
        return

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    for arch, shape in cells:
        for mp in meshes:
            try:
                emit(run_cell(arch, shape, multi_pod=mp))
            except Exception as e:  # record the failure, keep going
                emit({"arch": arch, "shape": shape,
                      "mesh": "2x16x16" if mp else "16x16",
                      "error": f"{type(e).__name__}: {e}",
                      "trace": traceback.format_exc()[-2000:]})


import numpy as np  # noqa: E402  (after XLA_FLAGS on purpose)

if __name__ == "__main__":
    main()
