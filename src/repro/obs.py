"""The program's own names for what a profiler trace shows of a round.

Device stages are ``jax.named_scope`` names: each op of the one-chip
streamed round program carries the innermost one in its HLO
``op_name`` metadata. Kernels are the Pallas ``name=`` of each launch.
Host spans wrap the phases of ``run_rounds_slab``'s chunk loop as
``jax.profiler`` annotations, on the clock of the device planes; while
a profiler session is active each span is also kept in ``SPAN_RECORD``
as ``(name, start_ns, end_ns)`` on ``time.time_ns``. With no session a
span costs one check of ``TraceMe.is_enabled``.
"""

from __future__ import annotations

import collections
import time

import jax

# Device stages of the round program (jax.named_scope).
BROADCAST = "fl.broadcast"      # model broadcast and int8 downlink
CLIENT = "stream.client"        # clients' forward and backward
PACK = "stream.pack"            # gradient packing, clean and loss sums
DRAWS = "stream.draws"          # participation, fading, CMS, SR draws
TRANSMIT = "stream.transmit"    # streamed transmit launches
CHANNEL = "stream.channel"      # the finishing channel launch
UPDATE = "fl.update"            # server update and round metrics
CAST = "fl.cast"                # storage-dtype round trip of w
STAGES = (BROADCAST, CLIENT, PACK, DRAWS, TRANSMIT, CHANNEL, UPDATE, CAST)

# Pallas kernels (pl.pallas_call name=).
OTA_CHANNEL = "ota_channel"                   # fused transmit + channel
OTA_TRANSMIT = "ota_transmit"                 # one-shot transmit
OTA_TRANSMIT_STREAM = "ota_transmit_stream"   # accumulating transmit
OTA_TRANSMIT_QUANT = "ota_transmit_quant"     # quantize-on-write transmit
OTA_RECEIVE = "ota_receive"                   # dequantize + interference
ADAPTIVE_UPDATE = "adaptive_update"           # server update
KERNELS = (OTA_CHANNEL, OTA_TRANSMIT, OTA_TRANSMIT_STREAM, OTA_TRANSMIT_QUANT,
           OTA_RECEIVE, ADAPTIVE_UPDATE)

# Host spans of one chunk of run_rounds_slab.
CHUNK = "fl.chunk"              # the whole chunk (a step annotation)
KEYS = "fl.keys"                # round keys and their stack
BATCH = "fl.batch"              # client batches and their stack
DISPATCH = "fl.dispatch"        # the round program's call
SYNC = "fl.sync"                # reading the round metrics back
HISTORY = "fl.history"          # history records, dead-round count
HOOK = "fl.hook"                # chunk_hook (e.g. a checkpoint save)
SPANS = (CHUNK, KEYS, BATCH, DISPATCH, SYNC, HISTORY, HOOK)

SPAN_RECORD: collections.deque = collections.deque(maxlen=4096)
_TraceMe = jax.profiler.TraceAnnotation


class span:
    """A host span: a ``TraceAnnotation`` (a ``StepTraceAnnotation``
    where ``step`` is given), recorded in ``SPAN_RECORD``. Where no
    profiler session is active as it opens, it does nothing."""

    __slots__ = ("_name", "_step", "_ann", "_t0")

    def __init__(self, name: str, step: int | None = None):
        self._name, self._step, self._ann = name, step, None

    def __enter__(self):
        if _TraceMe.is_enabled():
            self._ann = (_TraceMe(self._name) if self._step is None else
                         jax.profiler.StepTraceAnnotation(
                             self._name, step_num=self._step))
            self._ann.__enter__()
            self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            SPAN_RECORD.append((self._name, self._t0, time.time_ns()))
            self._ann.__exit__(*exc)
            self._ann = None


class CompileCounter:
    """XLA backend compiles (and their seconds) and persistent
    compile-cache hits and misses, counted from construction until
    ``close()`` (``jax.monitoring`` events; jax records a miss as it
    writes the entry). A context manager closes it on exit."""

    def __init__(self):
        self.compiles, self.compile_s, self.hits, self.misses = 0, 0.0, 0, 0
        self._listeners = (self._duration, self._event)
        jax.monitoring.register_event_duration_secs_listener(self._listeners[0])
        jax.monitoring.register_event_listener(self._listeners[1])

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def programs(self) -> int:
        """Programs built so far: compiled, or loaded from the cache."""
        return self.compiles + self.hits

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def close(self) -> None:
        if self._listeners:
            jax.monitoring.unregister_event_duration_listener(
                self._listeners[0])
            jax.monitoring.unregister_event_listener(self._listeners[1])
            self._listeners = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
