"""From a profiler trace of the window to the per-layer metrics.

``load(path)`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
what the readers need, in a plain form that a test can hold as JSON:

    {"window_ns": [start, end],
     "devices": {plane: {"ops": [[name, start, dur, stack], ...],
                         "modules": [[name, start, dur], ...]}},
     "host": [[name, start, dur], ...]}        # the benchmark's own spans

All times are nanoseconds on the profiler's one clock. An op's name is
its HLO instruction text as the TPU trace gives it; ``stack`` is its JAX
name stack (the ``tf_op`` stat) where the trace has one (the TPU traces
of jax 0.9 do not). ``View`` holds that with the cell's counts, and gives
each reader in bench/metrics/ the interval arithmetic it needs.

The trace nests loop and call ops around the ops they run, so sums go
over leaf ops only. The program names none of its stages yet, so a
round is split at its Pallas calls (``Round``): the update is the call
that returns a tuple of state slabs, the MAC calls are the others, and
the last MAC call of a round is the finishing channel launch. That split
holds only for a round of the shape the harness knows: ``mac_calls``
single-output Pallas calls (one streamed transmit per client chunk and
the channel launch), then one update call. Where any traced round has
another shape, the stage metrics are not attributed: their readers
return nothing, and standard error says why.
"""

from __future__ import annotations

import glob
import os
import re
import sys

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def xplane_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        stack = _stat(ev, "tf_op") or ""
                        dev["ops"].append([ev.name, int(ev.start_ns),
                                           int(ev.duration_ns), str(stack)])
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append([ev.name, int(ev.start_ns),
                                               int(ev.duration_ns)])
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def union_ns(intervals) -> int:
    """Length of the union of [start, start + dur) intervals."""
    total, end = 0, None
    for s, d in sorted((s, d) for s, d in intervals if d > 0):
        e = s + d
        if end is None or s >= end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


PALLAS = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


def out_entries(op_text: str) -> int:
    """Largest element count among an HLO op's output shapes."""
    head = op_text.split(" = ", 1)[-1]
    head = head[:head.index(")") + 1] if head.startswith("(") else \
        head.split(" ", 1)[0]
    best = 0
    for dims in _SHAPE.findall(head):
        n = 1
        for x in dims.split(","):
            if x:
                n *= int(x)
        best = max(best, n)
    return best


def is_pallas(op_text: str) -> bool:
    return PALLAS in op_text


COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)(?:\(|$)")


def is_collective(op_text: str) -> bool:
    """An op that moves data between chips: by its HLO opcode (with its
    ``-start`` and ``-done`` halves where the compiler made it async)."""
    m = _OPCODE.search(op_text.split(" = ", 1)[-1])
    return bool(m) and m.group(1).startswith(COLLECTIVES)


def is_update(op_text: str) -> bool:
    """The server update's Pallas call: the one that returns a tuple of
    (rows, 128) state slabs."""
    return is_pallas(op_text) and op_text.split(" = ", 1)[-1].startswith("(")


def leaves(ops: list) -> list:
    """Ops that contain no other op (the trace nests loop and call ops
    around the ops they run), sorted by start."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt[1] >= o[1] + o[2]:
            out.append(o)
    return out


class Round:
    """One execution of the round program on one chip, split in phases
    at its MAC Pallas calls: ``client`` up to the end of the last
    streamed transmit, ``draws`` from there to the finishing MAC call,
    ``finish`` after it. ``shape_error`` says why the split does not
    hold, or is None where the round has the expected ``mac_calls``
    MAC calls followed by one update call."""

    def __init__(self, ops: list, mac_calls: int):
        self.ops = ops
        pallas = [i for i, o in enumerate(ops) if is_pallas(o[0])]
        mac = [i for i in pallas if not is_update(ops[i][0])]
        upd = [i for i in pallas if is_update(ops[i][0])]
        self.mac = [ops[i] for i in mac]
        self.update = [ops[i] for i in upd]
        last = bool(pallas) and pallas[-1] in upd
        self.shape_error = None
        if len(mac) != mac_calls or len(upd) != 1 or not last:
            self.shape_error = (
                f"{len(mac)} single-output and {len(upd)} tuple-output "
                f"Pallas calls{'' if last else ', the last not an update'}"
                f"; expected {mac_calls} and 1, the update last")
        if len(mac) >= 2:
            self.client = ops[:mac[-2]]
            self.draws = ops[mac[-2] + 1:mac[-1]]
        else:
            self.client, self.draws = [], []


class View:
    """A loaded trace with the cell's facts, handed to every reader.

    ``planes`` are all the chips the trace holds; ``devices`` the ones the
    readers read. On one chip they are the same. On several, the readers
    read one chip, the one whose round-program executions take the most
    device time over the window, since every round waits for it; only
    ``mean_busy_s`` (the result's ``busy_s``, and ``idle_share``)
    averages over all of them.

    ``facts`` holds: ``rounds`` (rounds traced), ``chips``, ``peak`` (the
    chip's row of bench/counts.PEAKS), ``flops_round`` (model FLOPs of a
    round's clients), ``mac_bytes``, ``mac_ops``, ``update_bytes``,
    ``slab`` (entries of the state slab), ``mac_calls`` (MAC Pallas calls
    a round of the known shape makes), ``comm_bytes`` (the least bytes a
    chip sends in a round's exchange; 0 on one chip).
    """

    def __init__(self, trace: dict, facts: dict):
        self.trace, self.facts = trace, facts
        self.planes = sorted(trace["devices"])
        self.t0, self.t1 = trace["window_ns"]
        self._leaves, self._rounds = {}, {}
        self._told = False
        self.devices = self.planes
        if len(self.planes) > 1:
            self.devices = [max(self.planes, key=lambda d: sum(
                b - a for a, b in self.main_module(d)))]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def ops(self, device: str):
        t0, t1 = self.t0, self.t1
        return [o for o in self.trace["devices"][device]["ops"]
                if o[1] < t1 and o[1] + o[2] > t0]

    def leaf_ops(self, device: str) -> list:
        if device not in self._leaves:
            self._leaves[device] = leaves(self.ops(device))
        return self._leaves[device]

    def main_module(self, device: str):
        """(start, end) of each execution of the round program (the
        module with the most device time) inside the window."""
        mods = self.trace["devices"][device]["modules"]
        if not mods:
            return []
        total = {}
        for n, _, d in mods:
            total[n] = total.get(n, 0) + d
        main = max(total, key=total.get)
        return sorted((s, s + d) for n, s, d in mods
                      if n == main and self.t0 <= s < self.t1)

    def rounds(self, device: str) -> list:
        if device not in self._rounds:
            lv = self.leaf_ops(device)
            out = []
            for a, b in self.main_module(device):
                out.append(Round([o for o in lv if a <= o[1] < b],
                                 self.facts["mac_calls"]))
            self._rounds[device] = out
        return self._rounds[device]

    def busy_s(self, device: str) -> float:
        t0, t1 = self.t0, self.t1
        clipped = []
        for _, s, d, _ in self.ops(device):
            a, b = max(s, t0), min(s + d, t1)
            clipped.append((a, b - a))
        return union_ns(clipped) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.planes) / len(self.planes)

    def per_round_ms(self, pick) -> float | None:
        """Mean over chips of the summed duration of ``pick(round)``'s
        ops, per round traced, in ms; None where nothing is found or
        where a round does not have the shape its split needs."""
        tot, n = 0, 0
        for dev in self.devices:
            for r in self.rounds(dev):
                if r.shape_error is not None:
                    if not self._told:
                        print(f"trace: a round on {dev} has {r.shape_error}:"
                              f" stage metrics not attributed",
                              file=sys.stderr, flush=True)
                        self._told = True
                    return None
                tot += sum(o[2] for o in pick(r))
                n += 1
        return 1e-6 * tot / n if n and tot else None

    def collective_ms(self, exposed: bool = False) -> float | None:
        """Device time per round of the collective ops in the round
        program's executions, on the chip read; with ``exposed``, only the
        time in which no other leaf op runs. A collective counts whether
        or not other ops run inside it (an async one's ``-done`` half can
        span them). None where no round has one."""
        tot, n, found = 0, 0, False
        for dev in self.devices:
            ops, lv = self.ops(dev), self.leaf_ops(dev)
            for a, b in self.main_module(dev):
                comm = [o[1:3] for o in ops
                        if a <= o[1] < b and is_collective(o[0])]
                rest = [o[1:3] for o in lv
                        if a <= o[1] < b and not is_collective(o[0])]
                found = found or bool(comm)
                tot += (union_ns(comm + rest) - union_ns(rest) if exposed
                        else union_ns(comm))
                n += 1
        return 1e-6 * tot / n if found else None

    def top_ops(self, k: int = 10) -> list:
        agg: dict = {}
        for dev in self.devices:
            for o in self.leaf_ops(dev):
                name = o[0].split(" = ")[0]
                agg[name] = agg.get(name, 0) + o[2]
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[n, d * 1e-9 / len(self.devices)] for n, d in top]

    def idle_gaps(self, k: int = 10) -> list:
        """The longest device-idle gaps of the first chip, each named by
        the benchmark host span that covers most of it."""
        dev = self.devices[0]
        iv = sorted((s, s + d) for _, s, d, _ in self.ops(dev))
        gaps, end = [], self.t0
        for s, e in iv:
            if s > end:
                gaps.append((end, s))
            end = max(end, e)
        if self.t1 > end:
            gaps.append((end, self.t1))
        named = []
        for a, b in gaps:
            best, cover = "host other", 0
            for n, s, d in self.trace["host"]:
                c = min(b, s + d) - max(a, s)
                if c > cover:
                    best, cover = n, c
            named.append([best, (b - a) * 1e-9])
        return sorted(named, key=lambda g: -g[1])[:k]
