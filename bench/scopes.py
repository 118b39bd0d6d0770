"""The program's own stage and span names, read back off a traced window.

The round program names its stages with ``jax.named_scope``: every
instruction of the compiled program carries the innermost one in its
``op_name`` metadata, and the TPU trace names each device op by its
instruction. ``stage_of(view)`` maps instruction names to stages, from
``compiled.as_text()`` of the very executable the window ran.
The program's round loop names its host phases with spans (``fl.*``);
``program_spans(view)`` puts them on the trace's clock.

``STAGES`` and ``SPANS`` are this benchmark's copy of the names, kept
here on purpose and not imported from the program: a program that drops
or renames a stage shows as ``stage_unnamed_ms`` rising, not as a reader
that silently follows it.

Both maps are taken from ``view.facts["stage_of"]`` and
``view.trace["spans"]`` where the caller has put them there (the tests
do), and are otherwise found in the process that ran the window: the
live executable whose instructions name the window's round-program ops,
and ``repro.obs.SPAN_RECORD``. A program without the names (an older
commit) gives empty maps, and every reader here returns None.
"""

from __future__ import annotations

import re
import sys
import weakref

STAGES = ("fl.broadcast", "stream.client", "stream.pack", "stream.draws",
          "stream.transmit", "stream.channel", "fl.update", "fl.cast")
SPANS = ("fl.chunk", "fl.keys", "fl.batch", "fl.dispatch", "fl.sync",
         "fl.history", "fl.hook")
# the benchmark's own span that the program's fl.batch span encloses
ANCHOR, ANCHORED_IN = "bench.batch", "fl.batch"

_STAGE = re.compile(r"(?<![\w.])(" + "|".join(map(re.escape, STAGES))
                    + r")(?![\w.])")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_TOTALS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def instr(op_text: str) -> str:
    """An op's HLO instruction name, as the trace gives it."""
    return op_text.split(" = ", 1)[0].strip().lstrip("%")


def stage_map(hlo_text: str) -> dict:
    """Instruction name -> stage, for the instructions of ``hlo_text``.

    An instruction whose ``op_name`` names a stage takes the innermost
    one; a fusion, the stage most of its fused instructions name. An
    instruction the compiler made (no metadata at all: the buffers and
    copies of a pad, a concatenation, a layout change) takes the stage
    of the nearest instruction it feeds, else of the nearest it reads,
    else of the loop or call that runs its computation. An op of the
    program outside every stage stays unnamed."""
    comp, stage, meta, args, fused = None, {}, set(), {}, {}
    members: dict = {}
    comp_of: dict = {}
    caller: dict = {}
    for line in hlo_text.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        members.setdefault(comp, []).append(name)
        comp_of[name] = comp
        op = _OP_NAME.search(rest)
        if op:
            meta.add(name)
            names = _STAGE.findall(op.group(1))
            if names:
                stage[name] = names[-1]
        called = _CALLS.findall(rest)
        if called and " fusion(" in rest:
            fused[name] = called[0]
        else:
            for c in called:
                caller[c] = name
        args[name] = [a for a in _REF.findall(rest.split(", metadata=")[0])
                      if a not in called]
    users: dict = {}
    for name, a_list in args.items():
        for a in a_list:
            users.setdefault(a, []).append(name)
    for name, fc in fused.items():
        if name not in stage:
            inner = [stage[i] for i in members.get(fc, ()) if i in stage]
            if inner:
                stage[name] = max(set(inner), key=inner.count)
            elif any(i in meta for i in members.get(fc, ())):
                meta.add(name)
    made = [n for n in args if n not in meta and n not in stage]
    for _ in range(3):      # a loop's body waits for the loop's own stage
        for name in made:
            if name not in stage:
                found = (_nearest(name, users, stage, meta)
                         or _nearest(name, args, stage, meta)
                         or stage.get(caller.get(comp_of[name])))
                if found:
                    stage[name] = found
    return stage


def _nearest(start: str, graph: dict, stage: dict, meta: set):
    """The stage of the nearest instruction named by one, walking
    ``graph`` from ``start`` through instructions without metadata."""
    seen, frontier = {start}, [start]
    while frontier:
        nxt = []
        for n in frontier:
            for m in graph.get(n, ()):
                if m in seen:
                    continue
                seen.add(m)
                if m in stage:
                    return stage[m]
                if m not in meta:
                    nxt.append(m)
        frontier = nxt
    return None


def _main_module(view, dev: str) -> str:
    total: dict = {}
    for n, _, d in view.trace["devices"][dev]["modules"]:
        total[n] = total.get(n, 0) + d
    return max(total, key=total.get).split("(", 1)[0] if total else ""


def _live_round_program(view) -> str:
    """HLO text of the live executable that ran the window's round
    program: among those of the module's name, the one whose
    instructions name most of the window's round-program ops."""
    import jax
    dev = view.devices[0]
    name = _main_module(view, dev)
    seen = {instr(o[0]) for a, b in view.main_module(dev)
            for o in view.leaf_ops(dev) if a <= o[1] < b}
    best, hits = "", 0
    for exe in jax.devices()[0].client.live_executables():
        try:
            text = exe.get_hlo_text()
        except (jax.errors.JaxRuntimeError, NotImplementedError):
            continue                # a backend without the text view
        if not text.startswith(f"HloModule {name},"):
            continue
        have = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", text,
                              re.M))
        if len(seen & have) > hits:
            best, hits = text, len(seen & have)
    return best


def stage_of(view) -> dict:
    if "stage_of" not in view.facts:
        view.facts["stage_of"] = (stage_map(_live_round_program(view))
                                  if view.devices else {})
    return view.facts["stage_of"]


def program_spans(view) -> list:
    """The program's ``fl.*`` host spans as ``[name, start, dur]`` on the
    trace's clock. The program records them on ``time.time_ns``; each
    benchmark ``bench.batch`` span runs inside one ``fl.batch``, in the
    same order, so the offset between the two clocks lies between the
    largest end-to-end and the least start-to-start difference of the
    pairs: the midpoint is off by at most half that bracket, the few
    microseconds the host takes to enter and leave the inner span."""
    if "spans" not in view.trace:
        view.trace["spans"] = _aligned_spans(view)
    return view.trace["spans"]


def _aligned_spans(view) -> list:
    try:
        from repro.obs import SPAN_RECORD
    except ImportError:
        return []
    rec = [r for r in SPAN_RECORD if r[0] in SPANS]
    inner = sorted((s, s + d) for n, s, d in view.trace["host"]
                   if n == ANCHOR)
    outer = sorted((s, e) for n, s, e in rec if n == ANCHORED_IN)
    if not inner or len(outer) < len(inner):
        return []
    pairs = list(zip(inner, outer[-len(inner):]))
    hi = min(i[0] - o[0] for i, o in pairs)
    lo = max(i[1] - o[1] for i, o in pairs)
    off = (lo + hi) // 2 if lo <= hi else hi
    return [[n, s + off, e - s] for n, s, e in rec]


def _stage_totals(view):
    """(ns per stage summed over the window's round-program executions,
    executions), or None where the program names no stage."""
    if view not in _TOTALS:
        names = stage_of(view)
        tot: dict = {}
        runs = 0
        for dev in view.devices:
            lv = view.leaf_ops(dev)
            for a, b in view.main_module(dev):
                runs += 1
                for o in lv:
                    if a <= o[1] < b:
                        st = names.get(instr(o[0]))
                        tot[st] = tot.get(st, 0) + o[2]
        _TOTALS[view] = (tot, runs) if names and runs else None
        if not names:
            print("scopes: the round program names none of the stages",
                  file=sys.stderr, flush=True)
    return _TOTALS[view]


def stage_ms(view, stages) -> float | None:
    """Device time per round of the leaf ops under ``stages`` (None: under
    no stage), mean over chips; every cell runs one round a program."""
    got = _stage_totals(view)
    if got is None:
        return None
    tot, runs = got
    return 1e-6 * sum(tot.get(s, 0) for s in stages) / runs


def _union(iv) -> list:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out


def _overlap(x: list, y: list) -> int:
    tot, i, j = 0, 0, 0
    while i < len(x) and j < len(y):
        tot += max(0, min(x[i][1], y[j][1]) - max(x[i][0], y[j][0]))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return tot


def gap_ms(view, spans) -> float | None:
    """Per chunk, ms of the device-idle time between successive
    round-program executions (what ``host_gap_ms`` measures) that the
    program's host spans ``spans`` cover; None without program spans."""
    cover = _union((s, s + d) for n, s, d in program_spans(view)
                   if n in spans)
    if not cover:
        print(f"scopes: no program span of {sorted(spans)} on the trace",
              file=sys.stderr, flush=True)
        return None
    tot, gaps = 0, 0
    for dev in view.devices:
        runs = view.main_module(dev)
        ops = view.ops(dev)
        for (_, e0), (s1, _) in zip(runs, runs[1:]):
            busy = _union((max(s, e0), min(s + d, s1)) for _, s, d, _ in ops
                          if s < s1 and s + d > e0)
            idle, t = [], e0
            for a, b in busy:
                if a > t:
                    idle.append([t, a])
                t = max(t, b)
            if s1 > t:
                idle.append([t, s1])
            tot += _overlap(idle, cover)
            gaps += 1
    return 1e-6 * tot / gaps if gaps else None
