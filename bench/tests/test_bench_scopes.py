"""Checks of the readers of the program's stage and span names
(bench/scopes.py and the ``stage_*`` and ``gap_*`` metrics).

On a made-up trace with a made-up stage map every reader gives a value
worked by hand; the stage map follows the compiler's own instructions to
the stage they serve; the program's spans, recorded on another clock,
are put on the trace's clock by the benchmark spans they enclose; on two
hymba rounds traced on a TPU v5e with the names, all seventeen readers
give the values read off that trace when it was recorded; and a trace of
a program that names nothing (the older stored trace) leaves
every new metric out while the eight older readers read as before.
"""

import collections
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import counts, scopes  # noqa: E402
from bench.cell import load_module, resolve  # noqa: E402
from bench.trace import View  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
MS = 1_000_000
NEW = ["stage_client_ms", "stage_slab_ms", "stage_transmit_ms",
       "stage_channel_ms", "stage_draws_ms", "stage_update_ms",
       "stage_unnamed_ms", "gap_feed_ms", "gap_sync_ms"]
OLD = ["idle_share", "host_gap_ms", "client_ms", "round_mfu", "mac_ms",
       "mac_roofline", "draws_ms", "update_roofline"]
PALLAS = ' custom_call_target="tpu_custom_call"'
STAGE_OF = {"fusion.5": "fl.broadcast", "fusion.1": "stream.client",
            "fusion.3": "stream.client", "fusion.2": "stream.pack",
            "closed_call.1": "stream.transmit", "fusion.4": "stream.draws",
            "closed_call.2": "stream.channel", "closed_call.3": "fl.update",
            "fusion.6": "fl.cast"}


def _read(view, name):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    return load_module(path, "m_" + name).read(view)


def _round(t0):
    """One round program of 7 ms, its ops nested in a loop op; copy.1
    lies under no stage."""
    ops = [["%while.1 = (f32[8]) while", t0, 7 * MS]]
    t = t0
    for name, dur in [("fusion.5 = f32[1000] fusion", 0.25),
                      ("fusion.1 = bf16[2,64] fusion", 1.0),
                      ("fusion.2 = f32[1000] fusion", 0.5),
                      ("closed_call.1 = f32[1,1024] custom-call" + PALLAS, 1.0),
                      ("fusion.3 = bf16[2,64] fusion", 1.0),
                      ("closed_call.1 = f32[1,1024] custom-call" + PALLAS, 1.0),
                      ("fusion.4 = f32[4,4] fusion", 0.5),
                      ("closed_call.2 = f32[1,1024] custom-call" + PALLAS, 1.0),
                      ("copy.1 = f32[1000] copy", 0.25),
                      ("fusion.6 = f32[1000] fusion", 0.25),
                      ("closed_call.3 = (f32[8,128], f32[8,128]) custom-call"
                       + PALLAS, 0.25)]:
        ops.append(["%" + name, t, int(dur * MS)])
        t += int(dur * MS)
    return [o + [""] for o in ops]


# host spans around the gap [7, 10) ms, in which a small program runs
# on the device from 8.0 to 8.5 ms
SPANS = [["fl.sync", 6.0, 7.4], ["fl.history", 7.4, 7.5],
         ["fl.keys", 7.6, 8.2], ["fl.batch", 8.2, 9.0],
         ["fl.dispatch", 9.0, 10.2]]


def _made_up(spans=True):
    ops = _round(0) + [["%fold = u32[2] fusion", 8 * MS, MS // 2, ""]] \
        + _round(10 * MS)
    raw = {"window_ns": [0, 20 * MS],
           "devices": {"/device:TPU:0": {
               "ops": ops,
               "modules": [["jit_run(1)", 0, 7 * MS],
                           ["jit__threefry_fold_in(2)", 8 * MS, MS // 2],
                           ["jit_run(1)", 10 * MS, 7 * MS]]}},
           "host": [["bench.batch", round(8.25 * MS), round(0.7 * MS)]]}
    if spans:
        raw["spans"] = [[n, round(a * MS), round((b - a) * MS)]
                        for n, a, b in SPANS]
    return raw


def _view(raw, stage_of=STAGE_OF):
    facts = {"rounds": 2, "chips": 1, "peak": counts.peaks("TPU v5 lite"),
             "flops_round": 1e12, "mac_bytes": 819e6, "mac_ops": 0,
             "update_bytes": 819e6, "slab": 1000, "mac_calls": 3}
    if stage_of is not None:
        facts["stage_of"] = stage_of
    return View(raw, facts)


def test_stage_and_gap_readers_on_a_made_up_trace():
    v = _view(_made_up())
    got = {n: _read(v, n) for n in NEW}
    want = {"stage_client_ms": 2.0, "stage_slab_ms": 0.25 + 0.5 + 0.25,
            "stage_transmit_ms": 2.0, "stage_channel_ms": 1.0,
            "stage_draws_ms": 0.5, "stage_update_ms": 0.25,
            "stage_unnamed_ms": 0.25,
            # idle [7, 8) and [8.5, 10): keys 7.6-8.0, batch 8.5-9.0,
            # dispatch 9.0-10.0; sync 7.0-7.4, history 7.4-7.5
            "gap_feed_ms": 0.4 + 0.5 + 1.0, "gap_sync_ms": 0.4 + 0.1}
    assert got == {k: pytest.approx(x) for k, x in want.items()}
    # the seven stages cover the round's 7 ms of leaf ops
    assert sum(got[n] for n in NEW[:7]) == pytest.approx(7.0)
    # feed + sync + 0.1 ms unattributed (7.5-7.6) is the host gap
    assert got["gap_feed_ms"] + got["gap_sync_ms"] + 0.1 == \
        pytest.approx(_read(v, "host_gap_ms"))


def test_stage_map_takes_the_innermost_stage_name():
    text = "\n".join([
        "HloModule jit_run, is_scheduled=true",
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(run)/while/body/stream.client/jvp(model)/dot_general"}',
        '  ROOT %closed_call.2 = f32[1,8]{1,0} custom-call(%a), custom_call_'
        'target="tpu_custom_call", metadata={op_name="jit(run)/fl.broadcast'
        '/stream.draws/pallas_call" source_file="x.py" source_line=3}',
        '  %copy.3 = f32[4]{0} copy(%fusion.1), metadata={op_name='
        '"jit(run)/while/body/transpose(jvp(stream.pack))/mul"}',
        '  %add.4 = f32[4]{0} add(%a, %b), metadata={op_name="jit(run)/'
        'stream.clientele/add"}',
        "  %param.5 = f32[4]{0} parameter(0)"])
    assert scopes.stage_map(text) == {"fusion.1": "stream.client",
                                      "closed_call.2": "stream.draws",
                                      "copy.3": "stream.pack"}
    assert scopes.instr("%fusion.1 = f32[4]{0} fusion") == "fusion.1"


def test_compiler_made_instructions_take_the_stage_they_serve():
    """No metadata: a pad's zero buffer and update take the stage of the
    launch they feed, a slice after it the stage it reads, a copy loop
    the stage of its while, a fusion the stage most of its members name;
    an op of the program outside every stage stays unnamed."""
    meta = ', metadata={op_name="jit(run)/while/body/%s"}'
    text = "\n".join([
        "HloModule jit_run, is_scheduled=true",
        "",
        "%fused_computation.1 (param_0: f32[8]) -> f32[8] {",
        "  %param_0 = f32[8]{0} parameter(0)",
        "  %convert.1 = f32[8]{0} convert(%param_0)" + meta % "stream.pack/convert",
        "  ROOT %dynamic-update-slice.1 = f32[8]{0} dynamic-update-slice("
        "%convert.1, %param_0)",
        "}",
        "",
        "%body.3 (param.1: (u32[], f32[8])) -> (u32[], f32[8]) {",
        "  %param.1 = (u32[], f32[8]) parameter(0)",
        "  %get-tuple-element.9 = f32[8]{0} get-tuple-element(%param.1), index=1",
        "  %dynamic-update-slice.9 = f32[8]{0} dynamic-update-slice("
        "%get-tuple-element.9, %get-tuple-element.9)",
        "  ROOT %tuple.9 = (u32[], f32[8]) tuple(%param.1, %dynamic-update-slice.9)",
        "}",
        "",
        "ENTRY %main.1 (p.1: f32[8]) -> f32[8] {",
        "  %p.1 = f32[8]{0} parameter(0)",
        "  %grad.1 = f32[8]{0} multiply(%p.1, %p.1)" + meta % "stream.client/mul",
        "  %fusion.2 = f32[8]{0} fusion(%grad.1), kind=kLoop, "
        "calls=%fused_computation.1",
        "  %broadcast.3 = f32[8]{0} broadcast(%p.1), dimensions={}",
        "  %pad.4 = f32[8]{0} dynamic-update-slice(%broadcast.3, %fusion.2)",
        "  %tx.5 = f32[8]{0} custom-call(%pad.4), custom_call_target="
        '"tpu_custom_call"' + meta % "stream.transmit/pallas_call",
        "  %slice.6 = f32[8]{0} dynamic-slice(%tx.5, %p.1)",
        "  %while.7 = (u32[], f32[8]) while(%slice.6), condition=%cond.3, "
        "body=%body.3",
        "  %get-tuple-element.8 = f32[8]{0} get-tuple-element(%while.7), index=1",
        "  %ch.8 = f32[8]{0} custom-call(%get-tuple-element.8)"
        + meta % "stream.channel/pallas_call",
        "  ROOT %iota.9 = s32[8]{0} iota()" + meta % "iota",
        "}"])
    got = scopes.stage_map(text)
    assert got["fusion.2"] == "stream.pack"           # its members
    assert got["broadcast.3"] == got["pad.4"] == "stream.transmit"
    assert got["slice.6"] == "stream.channel"         # feeds the loop
    assert got["while.7"] == "stream.channel"
    assert got["dynamic-update-slice.9"] == "stream.channel"   # its while
    assert "iota.9" not in got                        # program, no stage


def test_readers_on_a_chip_trace_with_names(monkeypatch):
    """Two rounds of hymba-l2.silo8 traced on a TPU v5e with the
    program's names (op names cut as in the older stored trace; the
    stage map of the round program's instructions and the program's
    spans stored with it): the values below were read by hand off the
    same trace when it was recorded."""
    from repro import obs
    monkeypatch.setattr(obs, "SPAN_RECORD", collections.deque())
    with gzip.open(os.path.join(DATA, "trace_v5e_hymba_named.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    _, facts = _stored_chip_trace()
    facts["stage_of"] = raw.pop("stage_of")
    v = View(raw, facts)
    assert len(v.main_module("/device:TPU:0")) == 2
    want = {"idle_share": 0.59349781, "host_gap_ms": 11.257636,
            "client_ms": 1688.7628, "round_mfu": 2.8664061,
            "mac_ms": 815.24083, "mac_roofline": 0.59971905,
            "draws_ms": 64.007242, "update_roofline": 68.993495,
            "stage_client_ms": 1608.3134, "stage_slab_ms": 202.18530,
            "stage_transmit_ms": 838.72258, "stage_channel_ms": 125.74197,
            "stage_draws_ms": 31.054294, "stage_update_ms": 27.651462,
            "stage_unnamed_ms": 0.946959, "gap_feed_ms": 7.282652,
            "gap_sync_ms": 3.804684}
    got = {n: _read(v, n) for n in want}
    assert got == {k: pytest.approx(x, rel=1e-6) for k, x in want.items()}
    stages = sum(got[n] for n in NEW[:7])
    assert stages < 1e3 * v.mean_busy_s() / 2          # a round's share
    assert got["stage_unnamed_ms"] < 0.02 * stages
    assert got["gap_feed_ms"] + got["gap_sync_ms"] > 0.9 * got["host_gap_ms"]


def test_program_spans_are_put_on_the_trace_clock(monkeypatch):
    """Spans recorded on another clock, 1e18 ns away, land where the
    benchmark span they enclose puts them: bench.batch (8.25-8.95 ms)
    sits 0.05 ms inside fl.batch (8.2-9.0 ms) at both ends."""
    from repro import obs
    far = 10 ** 18
    rec = collections.deque([(n, far + round(a * MS), far + round(b * MS))
                             for n, a, b in SPANS])
    monkeypatch.setattr(obs, "SPAN_RECORD", rec)
    v = _view(_made_up(spans=False))
    assert scopes.program_spans(v) == [[n, round(a * MS), round((b - a) * MS)]
                                       for n, a, b in SPANS]
    assert _read(v, "gap_feed_ms") == pytest.approx(1.9)


def _stored_chip_trace():
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
    return raw, facts


def test_a_program_that_names_nothing_reports_no_new_metric(monkeypatch):
    """The older stored trace, of a program without the names: no op
    carries a stage and the program recorded no span, so every new
    reader returns None; the eight older readers read as before."""
    from repro import obs
    monkeypatch.setattr(obs, "SPAN_RECORD", collections.deque())
    raw, facts = _stored_chip_trace()
    v = View(raw, facts)
    assert all(_read(v, n) is None for n in NEW)
    want = {"idle_share": 0.42251748, "host_gap_ms": 9.977446,
            "client_ms": 1687.0082, "round_mfu": 2.8679122,
            "mac_ms": 815.18171, "mac_roofline": 0.59976255,
            "draws_ms": 66.157974, "update_roofline": 68.993187}
    got = {n: _read(v, n) for n in OLD}
    assert got == {k: pytest.approx(x, rel=1e-6) for k, x in want.items()}
