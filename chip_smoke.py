"""Chip smoke test: serve LUBM queries on a TPU through the server's own
entry points, and check every answer.

  python chip_smoke.py              # one chip: jnp and pallas backends
  python chip_smoke.py --chips 4    # four chips: shard_map vs vmap

One chip: builds one LUBM university (scale 1.0) from --seed, partitions
it with WawPart into 3 shards vmapped on the chip (the CLI default), and
for backend "jnp" and then "pallas" warms the bucket engines the way
`python -m repro.launch.serve` warms them, serves the CLI's request stream
through `WorkloadServer.serve()` and through `submit()`/`drain()`, and
checks every answer against the numpy oracle (`engine/oracle`), zero
overflows, zero shed, at most one compiled engine per bucket, a Pallas
kernel in every pallas engine, and byte-identical answers across the two
backends.

Four chips (--chips 4): the same deployment on 4 WawPart shards served
through shard_map on a 4-device mesh (`--sharded`), compared with the
vmap run of the same partitioning on one chip and with the oracle. It
checks that each bucket program emits two all_gathers per WawPart cut,
and that the KG blocks land one per device.

The answer cache is off, so every request reaches an engine. Lines before
the last are informational (no number there is a claim). The last line is
one JSON object naming the device; a failed check exits non-zero before
it. Without a TPU the script prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

SCALE = 1.0          # one LUBM university, the smallest published dataset
N_REQUESTS = 256     # the CLI's request stream, cut to a smoke length
BATCH = 64           # the CLI's default batch


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str) -> None:
    if not ok:
        fail(msg)


def bucket_batches(server, stream, batch: int) -> dict:
    """{(bucket, padded batch size): requests} for every engine shape that
    serving `stream` in chunks of `batch` dispatches: dedup within each
    chunk's bucket, then pad to a power of two (as `serve()` does)."""
    from repro.engine.batch import dedup_requests, pad_requests_pow2
    shapes = {}
    for i in range(0, len(stream), batch):
        per: dict[int, list] = {}
        for name, pv in stream[i:i + batch]:
            bi, pi = server.route[name]
            per.setdefault(bi, []).append((pi, pv))
        for bi, reqs in per.items():
            bucket = server.buckets[bi]
            unique, _ = dedup_requests(reqs, bucket.n_params)
            padded = pad_requests_pow2(unique)
            shapes.setdefault((bi, len(padded)), padded)
    return shapes


def compile_report(server, stream, *, want_kernel: bool) -> dict:
    """Compile each bucket engine at every shape the stream uses, print its
    compile seconds and memory_analysis bytes; returns {shape: lowered}."""
    from repro.engine.batch import stage_batch
    st = server._state
    lowered = {}
    for (bi, b), reqs in sorted(bucket_batches(server, stream,
                                               BATCH).items()):
        bucket = server.buckets[bi]
        pd, params = stage_batch(bucket, reqs, mesh=server.mesh)
        t0 = time.perf_counter()
        low = server._engine(bucket).lower(st.tr, st.va, st.perms, pd,
                                           params)
        compiled = low.compile()
        secs = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        print(f"  bucket {bi} batch {b}: compile {secs:.2f} s, "
              f"temp {mem.temp_size_in_bytes} B, "
              f"argument {mem.argument_size_in_bytes} B, "
              f"output {mem.output_size_in_bytes} B", flush=True)
        check(("tpu_custom_call" in compiled.as_text()) == want_kernel,
              f"bucket {bi}: Pallas kernel present != {want_kernel}")
        lowered[(bi, b)] = (low, compiled)
    return lowered


def serve_and_check(server, stream, oracle) -> dict:
    """Warm as the CLI warms, serve `stream` through serve() and through
    submit()/drain(), check every answer; returns {template: answer}."""
    t0 = time.perf_counter()
    for i in range(0, len(stream), BATCH):
        server.warmup(stream[i:i + BATCH])
    print(f"  warmup {time.perf_counter() - t0:.2f} s", flush=True)
    server.reset_stats()

    t0 = time.perf_counter()
    results = []
    for i in range(0, len(stream), BATCH):
        results += server.serve(stream[i:i + BATCH])
    dt = time.perf_counter() - t0
    print(f"  serve(): {len(stream)} requests in {dt:.4f} s "
          f"({len(stream) / dt:.1f} queries/s)", flush=True)

    t0 = time.perf_counter()
    tickets = [server.submit(name, pv) for name, pv in stream]
    server.drain()
    dt = time.perf_counter() - t0
    print(f"  submit()/drain(): {len(stream)} requests in {dt:.4f} s "
          f"({len(stream) / dt:.1f} queries/s)", flush=True)
    check(all(t.done and t.error is None for t in tickets),
          "a submitted request was shed")
    results += [t.result for t in tickets]

    answers = {}
    for (name, _), res in zip(stream + stream, results):
        check(res is not None, f"{name}: shed")
        rows, n, overflow = res
        check(not overflow, f"{name}: capacity overflow")
        check(np.array_equal(rows, oracle[name]), f"{name}: != oracle")
        answers[name] = (rows.shape, rows.tobytes())
    stats = server.stats
    check(stats["shed"] == 0, f"shed={stats['shed']}")
    check(server.n_compiles <= server.n_buckets,
          f"{server.n_compiles} engines for {server.n_buckets} buckets")
    print(f"  {sum(int(r[1]) for r in results)} solutions, overflows=0, "
          f"shed=0, compiled engines={server.n_compiles} "
          f"(<= {server.n_buckets} buckets), "
          f"executed {stats['executed']}/{stats['served']}", flush=True)
    return answers


def one_chip(store, queries, stream, oracle) -> None:
    from repro.launch.serve import PipelineConfig, WorkloadServer, \
        build_partition
    t0 = time.perf_counter()
    part = build_partition("wawpart", store, queries, 3)
    print(f"partition: 3 WawPart shards {part.shard_sizes.tolist()} "
          f"({time.perf_counter() - t0:.2f} s)", flush=True)
    answers = {}
    for backend in ("jnp", "pallas"):
        t0 = time.perf_counter()
        server = WorkloadServer(queries, part, backend=backend,
                                answer_cache=False,
                                pipeline=PipelineConfig(deadline_ms=None,
                                                        max_batch=BATCH))
        print(f"backend {backend}: server built in "
              f"{time.perf_counter() - t0:.2f} s, KG block "
              f"{tuple(server.kg.triples.shape)}, {server.n_buckets} "
              f"buckets", flush=True)
        compile_report(server, stream, want_kernel=backend == "pallas")
        answers[backend] = serve_and_check(server, stream, oracle)
        print(f"backend {backend}: answers equal the oracle", flush=True)
        del server
    check(answers["jnp"] == answers["pallas"],
          "jnp and pallas answers differ")
    print("jnp and pallas answers are byte-identical", flush=True)


def four_chips(store, queries, stream, oracle) -> None:
    import jax

    from repro.engine.batch import bucket_collectives, count_hlo_collectives
    from repro.launch.mesh import make_engine_mesh
    from repro.launch.serve import PipelineConfig, WorkloadServer, \
        build_partition
    t0 = time.perf_counter()
    part = build_partition("wawpart", store, queries, 4)
    print(f"partition: 4 WawPart shards {part.shard_sizes.tolist()} "
          f"({time.perf_counter() - t0:.2f} s)", flush=True)
    mesh = make_engine_mesh(4)
    answers = {}
    for label, m in (("vmap", None), ("shard_map", mesh)):
        t0 = time.perf_counter()
        server = WorkloadServer(queries, part, mesh=m, answer_cache=False,
                                pipeline=PipelineConfig(deadline_ms=None,
                                                        max_batch=BATCH))
        print(f"{label}: server built in {time.perf_counter() - t0:.2f} s, "
              f"KG block {tuple(server.kg.triples.shape)}, "
              f"{server.n_buckets} buckets, cuts per bucket "
              f"{server.collective_counts()}", flush=True)
        lowered = compile_report(server, stream, want_kernel=False)
        if m is not None:
            tr = server._state.tr
            check(tr.sharding.device_set == set(mesh.devices.flat),
                  f"KG on {tr.sharding.device_set}, mesh {mesh.devices}")
            blocks = [s.data.shape[0] for s in tr.addressable_shards]
            check(blocks == [1] * 4, f"KG blocks per device {blocks}")
            print("  KG blocks: one per device on "
                  f"{sorted(d.id for d in tr.sharding.device_set)}",
                  flush=True)
            for (bi, b), (low, compiled) in sorted(lowered.items()):
                cuts = bucket_collectives(server.buckets[bi].signature)
                emitted = count_hlo_collectives(low.as_text())
                # the TPU compiler may combine or split the emitted
                # gathers; count its distinct collective channels
                channels = set(re.findall(
                    r"all-(?:gather|reduce)[^\n]*?channel_id=(\d+)",
                    compiled.as_text()))
                print(f"  bucket {bi} batch {b}: {cuts} cuts, {emitted} "
                      f"collectives emitted, {len(channels)} collective "
                      f"channels compiled", flush=True)
                check(emitted == 2 * cuts, f"bucket {bi}: {emitted} "
                      f"collectives for {cuts} cuts")
                check(bool(channels) == bool(cuts),
                      f"bucket {bi}: compiled collectives {channels}")
        answers[label] = serve_and_check(server, stream, oracle)
        print(f"{label}: answers equal the oracle", flush=True)
        del server
    check(answers["vmap"] == answers["shard_map"],
          "shard_map and vmap answers differ")
    print(f"shard_map answers are byte-identical to vmap on "
          f"{len(jax.devices())} devices", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="LUBM generator seed")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"no TPU with {args.chips} chip(s): JAX sees {devices}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    run(args.chips, args.seed)
    return 0


def run(chips: int, seed: int) -> None:
    """Every phase after the device check; prints the result line last."""
    import jax

    from repro.engine.oracle import evaluate_bgp
    from repro.engine.planner import choose_order
    from repro.launch.serve import (build_dataset, request_stream,
                                    use_compile_cache)
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())};"
          f" compile cache {use_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    store, queries = build_dataset("lubm", SCALE, seed=seed)
    print(f"dataset: LUBM scale {SCALE} seed {seed}, {len(store)} triples "
          f"({time.perf_counter() - t0:.2f} s)", flush=True)
    t0 = time.perf_counter()
    # the planner's join order keeps the oracle off cartesian products
    oracle = {q.name: evaluate_bgp(store, q, order=choose_order(q, store))
              for q in queries}
    print(f"oracle: {len(oracle)} templates "
          f"({time.perf_counter() - t0:.2f} s)", flush=True)
    stream = request_stream(queries, N_REQUESTS)

    if chips == 4:
        four_chips(store, queries, stream, oracle)
    else:
        one_chip(store, queries, stream, oracle)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    sys.exit(main())
