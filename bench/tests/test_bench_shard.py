"""The harness on several chips: the sharded engine driven as users drive
it, its exchange read off the trace, and the one-chip engine untouched.

The run on four chips forces four CPU devices, which jax fixes when it
starts, so it runs in a child process (Pallas in interpret mode).
"""

import gzip
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import counts  # noqa: E402
from bench.cell import resolve  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
BENCH = os.path.join(DATA, "BENCHMARK.json")
SEED = 2**31 + 11

FOUR_CHIPS = f"""
import argparse, json, os, sys
sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r}]
from bench import run
from bench.control import exchange_left_out
args = argparse.Namespace(workload="tiny-hybrid.tiny4-4chip", seed={SEED},
                          seconds=0.1, trace=0, trace_dir=None)
kw = dict(bench_file={BENCH!r}, data_dir={DATA!r}, need_tpu=False,
          cache=False)
sound = run.run(args, **kw)
with exchange_left_out():
    fault = run.run(args, **kw)
print(json.dumps({{"sound": sound, "fault": fault}}))
"""


def test_four_chip_cell_is_correct_and_a_wrong_sum_is_not():
    """Four chips, 4 silos a round: the sharded engine's three set-up
    rounds and window agree with the plain reference; with the exchange
    between chips left out (each chip keeps its own clients' sum) the
    same run is not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", FOUR_CHIPS],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    sound, fault = res["sound"], res["fault"]
    assert sound["device"]["count"] == 4
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert not fault["correct"], fault["checks"]
    gap = fault["checks"]["gnorm_gap"]
    assert gap["value"] > gap["limit"]


CONTROLS = f"""
import json, sys
sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r}]
from bench.control import readings
recs = list(readings("tiny-hybrid.tiny4-4chip", [{SEED}], {{{SEED}}},
                     {BENCH!r}, {DATA!r}, need_tpu=False, cache=False))
print(json.dumps(recs))
"""


def test_four_chip_controls_read_above_the_limits():
    """On four chips the controls (model and state one step down, the
    model alone), the half-batch fault and the exchange left out each
    fail the four-chip tiny cell's limits; the sound program passes."""
    from bench import compare
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CONTROLS],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    kinds = {r["kind"]: r for r in
             json.loads(out.stdout.strip().splitlines()[-1])}
    limits = resolve("tiny-hybrid.tiny4-4chip", BENCH, DATA).limits
    assert set(kinds) == {"program", "control", "control_model", "half",
                          "exchange"}
    for kind, rec in kinds.items():
        ok, rows = compare.judge({k: rec[k] for k in limits}, limits)
        assert ok == (kind == "program"), (kind, rows)


def test_one_chip_branch_is_the_programs_own_engine():
    """On one chip the harness builds the round program that
    ``repro.launch.train --backend pallas`` builds: the same lowered text
    as the program's runner made directly, with no mesh."""
    import jax
    import jax.numpy as jnp
    from bench import program as prog
    from repro.core import make_slab_round_runner
    cell = resolve("tiny-hybrid.tiny", BENCH, DATA)
    layout = cell.reference.layout(cell.conf)
    p = prog.Program(cell.conf, cell.mix, 1, layout)
    assert p.mesh is None and p.spec.shards == 1
    assert p.channel.backend == "pallas" and p.server.backend == "pallas"
    direct = make_slab_round_runner(lambda q, b: p.model.loss_fn(q, b),
                                    p.channel, p.server, p.fl, donate=True)
    init_key, key_fn = prog.round_keys(SEED)
    state = jax.eval_shape(p.init_state, init_key)
    fed = cell.mix["federation"]
    ks = jax.eval_shape(lambda: jnp.stack([key_fn(0)]))
    bs = {"tokens": jax.ShapeDtypeStruct(
        (1, fed["clients_per_round"], fed["batch"], fed["seq"]), jnp.int32)}
    assert (p.run_chunk.lower(state, ks, bs).as_text()
            == direct.lower(state, ks, bs).as_text())


def test_exchange_bytes_by_hand():
    # 3 f32 slabs of 1024 entries, each chip sending 3/4 of each
    assert counts.exchange_bytes(1024, 4) == 3 / 4 * 4 * 1024 * 3
    assert counts.exchange_bytes(1024, 1) == 0
    assert counts.exchange_bytes(1024, 2, slabs=1) == 2048


PLANES = [f"/device:TPU:{i}" for i in range(4)]


def _four_chip_trace(slow=2):
    """Two 7 ms rounds on each of four chips (9 ms on chip ``slow``):
    compute, an async all-gather whose done half spans a compute op, more
    compute, a synchronous all-reduce, and a 2 ms host gap."""
    devices = {}
    for i, plane in enumerate(PLANES):
        extra = 2_000_000 if i == slow else 0
        ops, mods = [], []
        for t0 in (0, 10_000_000 + extra):
            r = 7_000_000 + extra
            ops += [
                ["%while.1 = (f32[8]) while", t0, r, ""],
                ["%fusion.1 = bf16[2,64] fusion", t0, 3_000_000 + extra, ""],
                ["%all-gather-start = f32[4,256] all-gather-start",
                 t0 + 3_000_000 + extra, 100_000, ""],
                ["%fusion.2 = bf16[2,64] fusion", t0 + 3_200_000 + extra,
                 500_000, ""],
                ["%all-gather-done = f32[4,256] all-gather-done",
                 t0 + 3_100_000 + extra, 900_000, ""],
                ["%fusion.3 = f32[1024] fusion", t0 + 4_000_000 + extra,
                 1_000_000, ""],
                ["%all-reduce.7 = f32[2,1024] all-reduce",
                 t0 + 5_000_000 + extra, 1_000_000, ""],
                ["%fusion.4 = f32[256] fusion", t0 + 6_000_000 + extra,
                 1_000_000, ""],
            ]
            mods.append(["jit_run", t0, r])
        devices[plane] = {"ops": ops, "modules": mods}
    return {"window_ns": [0, 25_000_000], "devices": devices,
            "host": [["bench.batch", 7_500_000, 2_000_000]]}


def _view(trace, comm_bytes=200e6):
    from bench.trace import View
    facts = {"rounds": 2, "chips": 4, "peak": counts.peaks("TPU v5 lite"),
             "flops_round": 1e12, "mac_bytes": 0, "mac_ops": 0,
             "update_bytes": 0, "slab": 1024, "mac_calls": 1,
             "comm_bytes": comm_bytes}
    return View(trace, facts)


def _read(view, name):
    from bench.cell import load_module
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    return load_module(path, "m_" + name).read(view)


@pytest.mark.parametrize("slow", [0, 2, 3])
def test_readers_read_the_chip_the_round_waits_for(slow):
    """Of four chips the readers read the one whose round programs take
    longest; busy time (the result's ``busy_s``) averages all four."""
    v = _view(_four_chip_trace(slow))
    assert v.planes == PLANES and v.devices == [PLANES[slow]]
    busy = [v.busy_s(p) for p in PLANES]
    assert v.mean_busy_s() == pytest.approx(sum(busy) / 4)
    assert busy[slow] == pytest.approx(18e-3)
    # the gap between the slow chip's rounds: 10 - 9 ms ... 12 ms
    assert _read(v, "host_gap_ms") == pytest.approx(3.0)


def test_exchange_read_off_a_made_up_trace():
    """Collectives a round on the chip read: all-gather start 0.1 ms and
    done 0.9 ms, of which 0.5 ms under a compute op, all-reduce 1 ms:
    2 ms of collectives, 1.5 ms exposed. 200 MB at 200 GB/s is 1 ms."""
    v = _view(_four_chip_trace())
    assert _read(v, "exposed_comm_ms") == pytest.approx(1.5)
    assert _read(v, "comm_roofline") == pytest.approx(50.0)


def test_exchange_readers_find_nothing_on_one_chip():
    """A round with no collective op (the one-chip engine) leaves both
    exchange metrics out."""
    t = _four_chip_trace()
    for plane in PLANES:
        t["devices"][plane]["ops"] = [
            o for o in t["devices"][plane]["ops"] if "all-" not in o[0]]
    v = _view(t)
    assert _read(v, "exposed_comm_ms") is None
    assert _read(v, "comm_roofline") is None


def test_exchange_read_off_a_four_chip_trace(capsys):
    """Two rounds of hymba-l2.silo32-4chip traced on a four-chip v5e (op
    names cut to name, output type and opcode; all four chips kept). The
    readers read chip 0, whose round programs take longest by a hair. A
    round there runs three collectives: the model broadcast's all-gather
    (6.4 ms), the stacked partial and clean sums' all-reduce (28.1 ms:
    how the compiler lowers the round's psum_scatter) and the metrics'
    scalar all-reduce; nothing overlaps them. The numbers were read off
    the same trace when it was recorded. The one-chip shape readers find
    another round (8 transmits, no channel launch) and leave it out."""
    from bench.trace import View
    with gzip.open(os.path.join(DATA, "trace_v5e_silo32_4chip.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    cell = resolve("hymba-l2.silo32-4chip")
    layout = cell.reference.layout(cell.conf)
    dims = cell.reference.dims(cell.conf, 1024)
    padded = -(-counts.param_count(layout) // 512) * 512
    assert padded == 200107520
    facts = {"rounds": 2, "chips": 4, "peak": counts.peaks("TPU v5 lite"),
             "flops_round": 32 * counts.client_flops(layout, dims, 2, 1024),
             "mac_bytes": counts.mac_bytes(layout, 32),
             "mac_ops": counts.mac_ops(layout),
             "update_bytes": counts.update_bytes(layout), "slab": padded,
             "mac_calls": 33, "comm_bytes": counts.exchange_bytes(padded, 4)}
    v = View(raw, facts)
    assert len(v.planes) == 4 and v.devices == ["/device:TPU:0"]
    assert all(len(v.main_module(p)) == 2 for p in v.planes)
    want = {"exposed_comm_ms": 34.5701735, "comm_roofline": 26.047998862,
            "idle_share": 1.4597583540, "host_gap_ms": 22.039367,
            "round_mfu": 4.3009751633}
    got = {name: _read(v, name) for name in want}
    assert got == {k: pytest.approx(x, rel=1e-6) for k, x in want.items()}
    assert got["comm_roofline"] <= 100.0
    assert _read(v, "mac_ms") is None
    assert "stage metrics not attributed" in capsys.readouterr().err
