"""Fast checks of the benchmark harness (no chip, no program run).

They hold the harness to its contract: cells resolve their files by name
alone, names and units keep to the allowed characters, traffic is a
function of the seed with the cell's shapes, the FLOP and byte counts
match values worked by hand, the trace reduction gives known numbers on a
trace recorded on the chip, and without a TPU ``bench/run.py`` exits
non-zero with no result line.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import counts  # noqa: E402
from bench.cell import NAME_RE, UNIT_RE, resolve  # noqa: E402
from bench.traffic import Traffic  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
BENCH = os.path.join(ROOT, "BENCHMARK.json")


def _bench():
    with open(BENCH) as f:
        return json.load(f)


def test_benchmark_keys_and_names():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME_RE.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/")
        names.append(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    assert all(NAME_RE.match(n) for n in names), names
    for text in [b["command"], *[c["why"] for c in b["configs"]]]:
        assert "\t" not in str(text)
    assert len(json.dumps(b)) < 64 * 1024


@pytest.mark.parametrize("workload", [w["name"] for w in _bench()["workloads"]])
def test_cell_resolves_by_name(workload):
    cell = resolve(workload)
    assert cell.readers and cell.end_to_end
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    layout = cell.reference.layout(cell.conf)
    assert counts.param_count(layout) > 1e8       # a deployment's size
    assert cell.mix["engine"]["rounds_per_chunk"] == 1


def test_new_cell_needs_only_files(tmp_path):
    """A cell with a mix of its own resolves from new files and a new
    entry alone."""
    b = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    b["workloads"].append({"name": "tiny-mla.other", "config": "tiny-mla",
                           "traffic": "other", "chips": 1, "why": "test"})
    shutil.copytree(DATA, tmp_path / "data")
    mix = json.load(open(tmp_path / "data" / "traffic" / "tiny.json"))
    mix["federation"]["seq"] = 24
    json.dump(mix, open(tmp_path / "data" / "traffic" / "other.json", "w"))
    bf = tmp_path / "b.json"
    json.dump(b, open(bf, "w"))
    cell = resolve("tiny-mla.other", str(bf), str(tmp_path / "data"))
    assert cell.mix["federation"]["seq"] == 24
    assert cell.conf["name"] == "tiny-mla"


@pytest.mark.parametrize("traffic", ["silo8", "sonwp50", "silo32"])
def test_traffic_is_a_function_of_the_seed(traffic):
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                      traffic + ".json")))
    fed = mix["federation"]
    vocab = 1000
    a = Traffic(fed, vocab, 2**31 + 17)
    b = Traffic(fed, vocab, 2**31 + 17)
    c = Traffic(fed, vocab, 5)
    x = a.batch_of(4)
    assert x.shape == (fed["clients_per_round"], fed["batch"], fed["seq"])
    assert x.dtype == np.int32 and x.min() >= 0 and x.max() < vocab
    np.testing.assert_array_equal(x, b.batch_of(4))
    assert not np.array_equal(x, a.batch_of(5))
    assert not np.array_equal(x, c.batch_of(4))
    ids = a.clients(3)
    assert len(set(ids)) == fed["clients_per_round"]
    assert ids.max() < fed["population"]


def test_counts_by_hand():
    layout = {"embed": {"table": ((10, 4), "bfloat16")},
              "blocks": {"w": {"kernel": ((2, 4, 6), "bfloat16")},
                         "n": {"scale": ((2, 4), "float32")}},
              "unembed": {"kernel": ((4, 10), "bfloat16")}}
    # entries: 40 + 48 + 8 + 40 = 136; bytes: 2*128 + 4*8 = 288
    assert counts.param_count(layout) == 136
    assert counts.grad_bytes(layout) == 288
    assert counts.mac_bytes(layout, 3) == 3 * 288 + 4 * 136
    assert counts.update_bytes(layout) == 24 * 136
    assert counts.matmul_params(layout) == (48, 40)
    assert counts.attention_pairs(5, None) == 15
    assert counts.attention_pairs(5, 2) == 3 + 3 * 2
    dims = {"positions": 5, "layers": 2, "heads": 3, "qk": 2, "v": 2,
            "window": None}
    # fwd = 2*B*(48*5 + 40*5) + 2*B*2*3*15*(2+2), B = 1; x3 for backward
    assert counts.client_flops(layout, dims, 1, 5) == 3 * (880 + 720)
    with pytest.raises(KeyError):
        counts.peaks("TPU v99")
    p = counts.peaks("TPU v5 lite")
    assert counts.least_seconds(819e9, 0, p) == pytest.approx(1.0)


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "hymba-l2.silo8", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def _view(trace, rounds=2, slab=1000, mac_calls=3):
    from bench.trace import View
    facts = {"rounds": rounds, "chips": 1, "peak": counts.peaks("TPU v5 lite"),
             "flops_round": 1e12, "mac_bytes": 819e6, "mac_ops": 0,
             "update_bytes": 819e6, "slab": slab, "mac_calls": mac_calls}
    return View(trace, facts)


def _read(view, name):
    from bench.cell import load_module
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    return load_module(path, "m_" + name).read(view)


PALLAS = ' custom_call_target="tpu_custom_call"'


def _rnd(t0):
    return [
        ["%while.1 = (f32[8]) while", t0, 7_000_000, ""],
        ["%fusion.1 = bf16[2,64] fusion", t0, 1_000_000, ""],
        ["%fusion.2 = f32[1000] fusion", t0 + 1_000_000, 500_000, ""],
        ["%closed_call.1 = f32[1,1024] custom-call" + PALLAS,
         t0 + 1_500_000, 1_000_000, ""],
        ["%fusion.3 = bf16[2,64] fusion", t0 + 2_500_000, 1_000_000, ""],
        ["%closed_call.1 = f32[1,1024] custom-call" + PALLAS,
         t0 + 3_500_000, 1_000_000, ""],
        ["%fusion.4 = f32[4,4] fusion", t0 + 4_500_000, 500_000, ""],
        ["%closed_call.2 = f32[1,1024] custom-call" + PALLAS,
         t0 + 5_000_000, 1_000_000, ""],
        ["%closed_call.3 = (f32[8,128], f32[8,128]) custom-call" + PALLAS,
         t0 + 6_000_000, 500_000, ""],
    ]


def _made_up(rounds):
    return {"window_ns": [0, 20_000_000],
            "devices": {"/device:TPU:0": {
                "ops": rounds[0] + rounds[1],
                "modules": [["jit_run", 0, 7_000_000],
                            ["jit_run", 10_000_000, 7_000_000]]}},
            "host": [["bench.batch", 7_000_000, 2_000_000]]}


def test_trace_reduction_on_a_made_up_trace():
    """Two rounds in a 20 ms window: known busy, idle and per-layer time.
    A round nests its ops in a loop op; Pallas calls split it into the
    client phase, the draws and the finish."""
    v = _view(_made_up([_rnd(0), _rnd(10_000_000)]))
    # busy: the loop ops [0, 7) and [10, 17) of 20 ms
    assert v.busy_s("/device:TPU:0") == pytest.approx(14e-3)
    assert _read(v, "idle_share") == pytest.approx(30.0)
    # client phase: fusion.1 and fusion.3 (fusion.2 is slab-sized)
    assert _read(v, "client_ms") == pytest.approx(2.0)
    assert _read(v, "mac_ms") == pytest.approx(3.0)
    assert _read(v, "draws_ms") == pytest.approx(0.5)
    assert _read(v, "host_gap_ms") == pytest.approx(3.0)  # 7..10 ms idle
    # 819e6 B per round at 819 GB/s = 1 ms over 3 ms (MAC), 0.5 ms (update)
    assert _read(v, "mac_roofline") == pytest.approx(100 / 3)
    assert _read(v, "update_roofline") == pytest.approx(200.0)
    assert _read(v, "round_mfu") == pytest.approx(100 * 2e12 / (0.02 * 197e12))
    # idle: [7,10) mostly under the batch span, [17,20) after the last op
    assert v.idle_gaps(2) == [["bench.batch", pytest.approx(3e-3)],
                              ["host other", pytest.approx(3e-3)]]


def test_trace_reduction_on_a_chip_trace():
    """Two rounds of hymba-l2.silo8 traced on a TPU v5e (op names cut to
    name, output type and opcode): the reduction finds the round program
    twice, 9 MAC calls and 1 update call a round, and gives the numbers
    below, read by hand off the same trace when it was recorded."""
    import gzip
    from bench.trace import View
    with gzip.open(os.path.join(DATA, "trace_v5e_hymba.json.gz"), "rt") as f:
        raw = json.load(f)
    cell = resolve("hymba-l2.silo8")
    layout = cell.reference.layout(cell.conf)
    dims = cell.reference.dims(cell.conf, 1024)
    facts = {"rounds": 2, "chips": 1, "peak": counts.peaks("TPU v5 lite"),
             "flops_round": 8 * counts.client_flops(layout, dims, 2, 1024),
             "mac_bytes": counts.mac_bytes(layout, 8),
             "mac_ops": counts.mac_ops(layout),
             "update_bytes": counts.update_bytes(layout),
             "slab": 200107264, "mac_calls": 9}
    v = View(raw, facts)
    rounds = v.rounds("/device:TPU:0")
    assert len(rounds) == 2
    assert all(len(r.mac) == 9 and len(r.update) == 1 for r in rounds)
    busy = v.mean_busy_s()
    assert busy == pytest.approx(5.710619764) and busy < v.window_s
    want = {"idle_share": 0.42251748, "host_gap_ms": 9.977446,
            "client_ms": 1687.0082, "round_mfu": 2.8679122,
            "mac_ms": 815.18171, "mac_roofline": 0.59976255,
            "draws_ms": 66.157974, "update_roofline": 68.993187}
    got = {name: _read(v, name) for name in want}
    assert got == {k: pytest.approx(x, rel=1e-6) for k, x in want.items()}
    # the stages measured fit inside a round's busy time
    stages = got["client_ms"] + got["mac_ms"] + got["draws_ms"]
    assert stages < 1e3 * busy / 2
    assert v.idle_gaps(1)[0][1] < 0.01


def _tuple_mac(ops):
    """A MAC call that also returns statistics, as a tracked-alpha
    channel launch would."""
    return [[o[0].replace("f32[1,1024] custom-call",
                          "(f32[1,1024], f32[2]) custom-call"), *o[1:]]
            if o[0].startswith("%closed_call.2") else o for o in ops]


@pytest.mark.parametrize("shape", ["one_transmit_more", "two_transmits_less",
                                   "tuple_output_mac", "update_not_last"])
def test_trace_of_another_round_shape_is_not_attributed(shape, capsys):
    """A round whose Pallas calls are not the known transmits, channel
    launch and update leaves every stage metric out, and says so; the
    metrics that need no split are still read."""
    rnd = _rnd(10_000_000)
    if shape == "one_transmit_more":
        mac_calls = 2
    elif shape == "two_transmits_less":
        mac_calls = 5
    elif shape == "tuple_output_mac":
        mac_calls, rnd = 3, _tuple_mac(rnd)
    else:
        mac_calls = 4
        rnd.append(["%closed_call.4 = f32[1,1024] custom-call" + PALLAS,
                    16_600_000, 300_000, ""])
    v = _view(_made_up([_rnd(0), rnd]), mac_calls=mac_calls)
    for name in ("client_ms", "mac_ms", "draws_ms", "mac_roofline",
                 "update_roofline"):
        assert _read(v, name) is None, name
    assert "stage metrics not attributed" in capsys.readouterr().err
    assert _read(v, "idle_share") is not None
    assert _read(v, "host_gap_ms") == pytest.approx(3.0)


@pytest.mark.parametrize("block,key,value", [
    ("federation", "sample_rate", 0.1),
    ("engine", "double_buffer", True),
    ("engine", "rounds_per_chunk", 2),
    ("channel", "uplink", {"mode": "int8", "error_feedback": True}),
    ("channel", "fading", "none"),
    ("server", "alpha", "auto"),
    ("server", "optimizer", "yogi_ota"),
    ("top", "ckpt_every", 10),
])
def test_mix_the_reference_does_not_implement_is_refused(block, key, value):
    """A traffic mix runs what it names or fails: a key or a value that
    the program's wiring or the reference does not implement raises."""
    from bench import mix as mixes
    from bench.reference.fl import reference_rounds
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                      "silo8.json")))
    mixes.check(mix)
    (mix if block == "top" else mix[block])[key] = value
    with pytest.raises(ValueError):
        mixes.check(mix)
    with pytest.raises(ValueError):
        reference_rounds(None, {}, [], [], mix)


def test_program_refuses_a_mesh():
    """A cell on more chips than jax sees fails before anything is
    built."""
    import jax
    from bench.program import Program
    cell = resolve("hymba-l2.silo8")
    chips = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"needs {chips} devices"):
        Program(cell.conf, cell.mix, chips, cell.reference.layout(cell.conf))
