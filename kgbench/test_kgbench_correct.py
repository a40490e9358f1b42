"""The check that decides `correct` can fail: its control, and faults
planted in the program under a whole run (CPU, tiny size, chip check
skipped)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kgbench import calibrate, harness, testkit
from kgbench.traffic import Mix


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "enable_compile_cache", lambda: "off")
        yield testkit.tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_fails_where_the_program_passes(root, seed, monkeypatch):
    """The control (answers cut to a static capacity, no overflow flag)
    must read as not correct on the same requests the program passes."""
    # a cap the tiny graph's largest answers exceed
    monkeypatch.setattr(calibrate, "CONTROL_CAP", 16)
    cell = harness.resolve("lubm-zipf-open", root)
    dep = harness.build(cell, seed)
    harness.warmup(dep)
    mix = Mix(cell.traffic, harness.domains(cell, dep.graph), seed)
    reqs, _, _ = harness.serve_window(dep, mix, mix.open_schedule(3.0), 3.0)
    dep.server.drain()
    prog = harness.check(dep, cell, reqs, reqs)
    ctrl = calibrate.control_check(dep, cell, reqs)
    assert prog.correct and prog.mismatched == 0
    assert not ctrl.correct and ctrl.mismatched > 0


def _join_leaves_table(table, tmask, *args, **kw):
    return table, tmask, jnp.zeros((), bool)


def _exchange_left_out(x, axis_name, **kw):
    # every shard sees only its own matches: the others' arrive empty
    return jnp.stack([x] + [jnp.zeros_like(x)] * 2)


def _half_batch(reqs):
    from repro.engine.batch import pad_requests_pow2
    keep = (len(reqs) + 1) // 2
    return pad_requests_pow2(reqs[:keep] + [(0, None)] * (len(reqs) - keep))


def _altered(bucket, unique, inverse, *out):
    from repro.engine.batch import extract_fanout
    res = extract_fanout(bucket, unique, inverse, *out)
    for i, (rows, n, ovf) in enumerate(res):
        if n:
            rows = rows.copy()
            rows[0, 0] += 1
            res[i] = (rows, n, ovf)
            break
    return res


FAULTS = {
    "step_returns_state_unchanged": [
        ("repro.engine.batch", "_join_merge", _join_leaves_table),
        ("repro.engine.batch", "_join_data", _join_leaves_table)],
    "exchange_between_shards_left_out": [
        (jax.lax, "all_gather", _exchange_left_out)],
    "half_of_the_batch_left_out": [
        ("repro.launch.serve", "pad_requests_pow2", _half_batch)],
    "answer_altered_where_produced": [
        ("repro.launch.serve", "extract_fanout", _altered)],
}


@pytest.mark.parametrize("seed", [8, 2**33 + 1])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_makes_the_run_incorrect(
        root, no_cache, monkeypatch, fault, seed):
    cell = "lubm-zipf-open"
    if fault == "exchange_between_shards_left_out":
        dep = harness.build(harness.resolve(cell, root), seed)
        assert sum(dep.server.collective_counts()) > 0
    import importlib
    for target, name, fn in FAULTS[fault]:
        if isinstance(target, str):
            target = importlib.import_module(target)
        monkeypatch.setattr(target, name, fn)
    res = testkit.run_cpu(root, cell, seed=seed)
    assert res["correct"] is False
    assert res["failed"] > 0
    assert res["checks"]["mismatched"]["value"] > 0


def test_overflow_and_unanswered_count_as_failed(root, no_cache,
                                                 monkeypatch):
    from repro.launch import serve

    def dropped_flag(bucket, unique, inverse, *out):
        from repro.engine.batch import extract_fanout
        res = extract_fanout(bucket, unique, inverse, *out)
        return [(r, n, True) for r, n, _ in res[:1]] + res[1:]

    monkeypatch.setattr(serve, "extract_fanout", dropped_flag)
    res = testkit.run_cpu(root, "lubm-zipf-open", seed=9)
    assert res["correct"] is False
    assert res["checks"]["overflowed"]["value"] > 0
    np.testing.assert_equal(res["failed"] >= res["checks"]["overflowed"]
                            ["value"], True)
