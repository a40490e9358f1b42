"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships:
  kernel.py — pl.pallas_call body with explicit BlockSpec VMEM tiling,
  ops.py    — jit'd public wrapper (platform dispatch: TPU kernel / CPU
              interpret / jnp reference),
  ref.py    — pure-jnp oracle used by tests (assert_allclose sweeps).

Kernels: jaccard (WawPart distance matrix), flash_attention (LM prefill),
segment_spmm (GNN message passing), embedding_bag (recsys lookup),
cin (xDeepFM interaction), kg_scan (fused masked triple-pattern scan for
the query engines' backend="pallas"), kg_join (blocked merge-join
candidate ranges + expand-join compat matrix, same backend).

The kg_* kernels' refs delegate to engine/primitives — the deduplicated
scan/join logic is simultaneously the jnp execution backend and the
kernel oracle.
"""
import jax


def default_interpret() -> bool:
    """Pallas kernels execute natively on TPU; everywhere else we run the
    kernel body in interpret mode (Python on CPU) for correctness."""
    return jax.default_backend() != "tpu"


def round_up(n: int, m: int) -> int:
    """n rounded up to a multiple of m (padding to whole kernel tiles)."""
    return -(-n // m) * m
