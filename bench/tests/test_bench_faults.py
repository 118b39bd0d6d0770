"""The comparison that decides ``correct`` fails on a broken timed path.

Each test skips the harness's look for a chip and drives the rest of a
run of ``bench/run.py`` at a tiny size on the CPU (Pallas in interpret
mode), with the timed path broken underneath:

* a round that returns its state unchanged;
* half of every client's batch left out, the mean taken over the rest;

and the controls, the plain reference put in the program's place one
precision step down (model and state, and the model alone), read above
the limits. The sound run passes. The
tiny cell's limits (bench/tests/data/limits/) were set from the same
kind of readings at this size.
"""

import argparse
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

DATA = os.path.join(ROOT, "bench", "tests", "data")
BENCH = os.path.join(DATA, "BENCHMARK.json")
WORKLOAD = "tiny-hybrid.tiny"
SEED = 2**31 + 11


def _run(**kw):
    from bench import run
    args = argparse.Namespace(workload=WORKLOAD, seed=SEED, seconds=0.2,
                              trace=0, trace_dir=None)
    return run.run(args, bench_file=BENCH, data_dir=DATA, need_tpu=False,
                   cache=False, **kw)


def _unchanged(call):
    import jax.numpy as jnp

    def broken(state, keys, batches):
        before = (state.w.copy(), tuple(o.copy() for o in state.opt))
        new, metrics = call(state, keys, batches)
        return new.__class__(new.step, before[0], before[1], new.alpha_hat,
                             new.spec, new.ef), metrics

    del jnp
    return broken


def _half_batch_loss():
    from bench.cell import resolve
    from bench.program import model_config
    from repro.models.model import build_model
    cell = resolve(WORKLOAD, BENCH, DATA)
    model = build_model(model_config(cell.conf))

    def loss(p, b):
        tok = b["tokens"]
        return model.loss_fn(p, {"tokens": tok[:tok.shape[0] // 2]})

    return loss


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"round_s", "hbm_gb", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_timed_path_is_not_correct(fault):
    kw = ({"wrap_chunk": _unchanged} if fault == "unchanged"
          else {"loss_fn": _half_batch_loss()})
    res = _run(**kw)
    assert not res["correct"], res["checks"]


def test_control_reads_above_the_limits():
    from bench import compare
    from bench.cell import resolve
    from bench.control import readings
    cell = resolve(WORKLOAD, BENCH, DATA)
    recs = list(readings(WORKLOAD, [SEED], {SEED}, BENCH, DATA,
                         need_tpu=False, cache=False))
    kinds = {r["kind"]: r for r in recs}
    for kind in ("control", "control_model", "half"):
        nums = {k: kinds[kind][k] for k in cell.limits}
        ok, rows = compare.judge(nums, cell.limits)
        assert not ok, (kind, rows)
    nums = {k: kinds["program"][k] for k in cell.limits}
    assert compare.judge(nums, cell.limits)[0]
