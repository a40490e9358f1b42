"""Compile every shape a cell warms up for a described TPU v5e, without
the chip, and print each program's memory_analysis bytes.

  JAX_PLATFORMS=cpu python3 kgbench/rehearse.py lubm-zipf-open

The deployment is built on the CPU at the cell's full size; each
(bucket, batch) shape that the cell's warm-up runs is lowered through the
server's engine cache and compiled for one chip of a described v5e:2x2.
Nothing runs, so this gives bytes and refusals, never times. Prints one
JSON line per shape.
"""
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from kgbench import harness
    from repro.engine.batch import PlanData

    cell = harness.resolve(sys.argv[1], ROOT)
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    dep = harness.build(cell, 0)
    srv, kg = dep.server, dep.server.kg
    i32 = jnp.int32

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=chip)

    for bi, b, _ in harness.warm_shapes(dep):
        bucket = srv.buckets[bi]
        sig = bucket.signature
        fn = srv.cache.get(sig, join_impl=srv.join_impl,
                           max_per_row=srv.max_per_row,
                           gather_cap=srv.gather_cap, mesh=srv.mesh,
                           backend=srv.backend,
                           kernel_blocks=srv.kernel_blocks)
        L = sig.n_steps
        pd = PlanData(*(shape(s, d) for s, d in (
            ((b, L, 3), i32), ((b, L, 3), i32), ((b, L, 3), jnp.bool_),
            ((b, L, 3), i32), ((b, L, 3), i32),
            ((b, L, kg.n_shards), jnp.bool_), ((b, L), jnp.bool_))))
        mem = fn.lower(shape(kg.triples.shape, i32),
                       shape(kg.valid.shape, jnp.bool_),
                       shape((kg.n_shards, 3, kg.cap), i32), pd,
                       shape((b, bucket.n_params), i32)
                       ).compile().memory_analysis()
        print(json.dumps({
            "cell": cell.name, "bucket": bi, "batch": b,
            "templates": [p.query.name for p in bucket.plans],
            "table_cap": sig.table_cap,
            "temp_bytes": mem.temp_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
