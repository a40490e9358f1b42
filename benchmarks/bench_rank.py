"""Rank-search bench: the engine's searchsorted formulations at the LUBM
bucket programs' own shapes, and the keys-per-block crossover that sets
`primitives.RANK_COMPARE_MAX_KEYS`.

Each case runs one formulation jitted alone, vmapped over 3 shards (the
served configuration's vmapped WawPart shards), and checks it against
numpy's searchsorted before any time is reported. Formulations:

  * scan        — jnp.searchsorted's binary search: one round of dependent
                  gathers per level;
  * compare_all — count the keys below each query: no gather, one reduce;
  * sort        — jnp.searchsorted(method="sort");
  * pallas      — the `kg_join_ranges` counting kernel (join sites, TPU).

Sites (bucket 4 = the widest LUBM bucket, `jit_kg_L6_V4_R32768`):
  join      — merge-join candidate ranges, both sides, B blocks of C keys,
              Q table-row keys;
  compact   — `select_from_cum`: arange(1, Q + 1) into a C-long cumsum;
  windows   — `_select_windows`: arange(Q) into a C-long cumsum, right.
The `cross` cases time scan against compare_all at 32,768 queries into
ever longer blocks, past the crossover.

Times are host-timed medians of 10 calls after a warm one, dispatch
included; only a chip run's numbers are chip numbers.

    PYTHONPATH=src python benchmarks/bench_rank.py --json rank.jsonl
    PYTHONPATH=src python benchmarks/bench_rank.py --smoke   # CPU rot-guard
"""
from __future__ import annotations

import argparse
import json
import time

S = 3                     # vmapped shards
INT_MAX = 2**31 - 1
MAX_TEMP_BYTES = 8 * 2**30   # a case whose temp exceeds it is not run

# (site, blocks, keys per block, queries, methods)
JOIN = ("scan", "compare_all", "sort", "pallas")
ONE_SIDE = ("scan", "compare_all", "sort")
CASES = [
    ("join", 3, 16384, 32768, JOIN),    # bucket 4, steps 3 and 4
    ("join", 1, 16384, 32768, JOIN),    # bucket 4, step 2
    ("join", 1, 36800, 32768, JOIN),    # bucket 4, step 5
    ("join", 1, 2048, 32768, JOIN),     # bucket 4, step 1
    ("join", 1, 36800, 256, JOIN),      # bucket 3 (Q7)
    ("join", 1, 2048, 2048, JOIN),      # bucket 1
    ("compact", 1, 36800, 36800, ONE_SIDE),   # bucket 4, step 5
    ("compact", 1, 36800, 16384, ONE_SIDE),   # bucket 4, steps 2-4
    ("compact", 1, 36800, 1024, ONE_SIDE),
    ("compact", 1, 36800, 256, ONE_SIDE),
    ("windows", 1, 98304, 32768, ONE_SIDE),   # bucket 4, step 3
    ("windows", 1, 32768, 32768, ONE_SIDE),   # bucket 4, steps 1, 2, 4
] + [("cross", 1, c, 32768, ("scan", "compare_all"))
     for c in (65536, 131072, 262144, 524288)]
SMOKE = [("join", 2, 64, 96, JOIN), ("compact", 1, 200, 150, ONE_SIDE),
         ("windows", 1, 300, 128, ONE_SIDE),
         ("cross", 1, 512, 64, ("scan", "compare_all"))]


def _data(rng, site, B, C, Q):
    """(keys (S, B, C) sorted per block, queries (S, Q), sides)."""
    import numpy as np
    if site in ("join", "cross"):
        keys = np.full((S, B, C), INT_MAX, np.int32)
        for s in range(S):
            for b in range(B):
                live = int(C * rng.uniform(0.05, 0.9))
                keys[s, b, :live] = np.sort(rng.integers(0, 60000, live))
        q = rng.integers(0, 60000, (S, Q)).astype(np.int32)
        q[rng.uniform(size=(S, Q)) < 0.5] = -1
        return keys, q, ("left", "right")
    if site == "compact":
        mask = rng.uniform(size=(S, B, C)) < rng.uniform(0.001, 0.5)
        q = np.arange(1, Q + 1, dtype=np.int32)
        return (np.cumsum(mask, axis=-1).astype(np.int32),
                np.broadcast_to(q, (S, Q)).copy(), ("left",))
    n = rng.integers(0, 9, (S, B, C)) * (rng.uniform(size=(S, B, C)) < .05)
    q = np.arange(Q, dtype=np.int32)
    return (np.cumsum(n, axis=-1).astype(np.int32),
            np.broadcast_to(q, (S, Q)).copy(), ("right",))


def _fn(method, sides):
    import jax

    from repro.engine.primitives import _ranks
    if method == "pallas":
        from repro.kernels.kg_join.ops import join_ranges
        return jax.vmap(lambda k, q: join_ranges(k, q))
    return jax.vmap(lambda k, q: _ranks(k, q, sides, method))


def run(cases, reps: int = 10, seed: int = 7) -> list[dict]:
    import jax
    import numpy as np
    rng = np.random.default_rng(seed)
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    for site, B, C, Q, methods in cases:
        keys, q, sides = _data(rng, site, B, C, Q)
        want = [np.stack([np.stack([np.searchsorted(k, q[s], side=sd)
                                    for k in keys[s]]) for s in range(S)])
                for sd in sides]
        args = (jax.numpy.asarray(keys), jax.numpy.asarray(q))
        for method in methods:
            if method == "pallas" and not on_tpu:
                continue
            row = {"site": site, "blocks": B, "keys": C, "queries": Q,
                   "method": method}
            t0 = time.perf_counter()
            compiled = jax.jit(_fn(method, sides)).lower(*args).compile()
            row["compile_s"] = time.perf_counter() - t0
            row["temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
            if row["temp_bytes"] > MAX_TEMP_BYTES:   # would not fit: skip
                print(json.dumps(row), flush=True)
                rows.append(row)
                continue
            out = jax.block_until_ready(compiled(*args))
            row["equal"] = all(np.array_equal(np.asarray(o), w)
                               for o, w in zip(out, want))
            ts = []
            for _ in range(reps):
                t = time.perf_counter()
                jax.block_until_ready(compiled(*args))
                ts.append(time.perf_counter() - t)
            row["ms_median"] = 1e3 * float(np.median(ts))
            row["ms_min"] = 1e3 * min(ts)
            print(json.dumps(row), flush=True)
            assert row["equal"], row
            rows.append(row)
    return rows


def main(argv: list[str] | None = None) -> list[dict]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, one timed call (CPU rot-guard)")
    ap.add_argument("--only", nargs="*", default=None,
                    help="run only these sites (join, compact, windows, "
                         "cross)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as JSON lines")
    args = ap.parse_args(argv)
    cases = SMOKE if args.smoke else CASES
    if args.only:
        cases = [c for c in cases if c[0] in args.only]
    rows = run(cases, reps=1 if args.smoke else 10)
    if args.json:
        with open(args.json, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    return rows


if __name__ == "__main__":
    main()
