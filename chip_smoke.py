"""Chip smoke test: the federated OTA training loop, compiled, on a TPU.

Runs ``repro.launch.train``'s ``main`` — the entry point of
``python -m repro.launch.train`` — on hymba-1.5b at its published widths
(d_model 1600, 25 heads with 5 KV heads, d_ff 5504, vocab 32001, window
1024, SSM state 16, 128 meta tokens) with its depth cut to ``LAYERS``,
for ``ROUNDS`` federated rounds of the slab engine with compiled Pallas
kernels. Then, in the same process and from the same seed, it runs the
comparison path and checks that the two loss and grad-norm histories
agree within ``RTOL``.

    python chip_smoke.py             # one chip: --backend pallas vs jnp
    python chip_smoke.py --chips 4   # (4,) client mesh: pallas_sharded
                                     # vs the single-device pallas run

It exits non-zero, and prints no ok line, where jax finds no TPU, where
Pallas interpret mode is asked for, on a non-finite loss, or when the
comparison fails. Its last line on stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro import obs  # noqa: E402
from repro.kernels.interpret import INTERPRET_ENV, resolve_interpret  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "hymba-1.5b"
# Depth cut: what one 16 GB chip holds of the federated state plus one
# client's forward/backward at seq 1024 (see CHANGES.md for the sizing).
LAYERS = 2
ROUNDS = 6
SCAN_ROUNDS = 2          # chunk 1 compiles; chunks 2.. are steady state
# Both paths run the same model forward/backward (default-precision
# matmuls) on the same batches and draws. What differs is the MAC and
# the server update: the Pallas kernels against XLA's fused jnp ops
# (transcendentals of the CMS transform and of |Delta|^alpha), and on
# the mesh the order of the cross-device client sum. On the CPU those
# agree to ~1e-5 per round in f32 (tests/test_backend_parity.py); the
# bf16 weight cast the model reads then turns isolated f32 ulps into
# bf16 ulps, and six rounds of training carry them on. On a TPU v5e the
# pallas and jnp histories of this run agreed to 1.05e-4. 1e-3 relative
# leaves ten times that and still fails on a real fault: a wrong
# aggregate or update moves the loss by far more after one round.
RTOL = 1e-3

# The trainer's default lr (0.02) moves every weight of a 200M-parameter
# model by ~0.02 per round under the adaptive update, and the loss then
# climbs (10.8 -> 15.5 in 6 rounds on the chip), a regime that amplifies
# any rounding difference between the two paths; 1e-3 trains.
BASE_ARGS = ["--arch", ARCH, "--preset", "full", "--layers", str(LAYERS),
             "--clients", "8", "--client-chunk", "1", "--batch", "2",
             "--seq", "1024", "--optimizer", "adam_ota", "--uplink", "f32",
             "--lr", "1e-3", "--rounds", str(ROUNDS),
             "--scan-rounds", str(SCAN_ROUNDS),
             "--log-every", str(SCAN_ROUNDS), "--seed", "0",
             # compiles the round program before the first chunk and
             # reports the memory analysis of that executable
             "--donation-report"]


class Fail(Exception):
    """A check of the smoke test failed."""


def peak_bytes_in_use() -> str:
    """Peak device memory of this process so far (first chip)."""
    stats = jax.devices()[0].memory_stats()
    return f"{stats['peak_bytes_in_use']} of {stats.get('bytes_limit')}"


def cache_contents(path: str) -> str:
    """Entries and bytes in the compile cache, with its largest entries:
    what a later call can be served from."""
    sizes = {}
    if os.path.isdir(path):
        for name in os.listdir(path):
            full = os.path.join(path, name)
            if os.path.isfile(full):
                sizes[name] = os.path.getsize(full)
    top = sorted(sizes.items(), key=lambda kv: -kv[1])[:4]
    return (f"{len(sizes)} entries, {sum(sizes.values())} bytes; largest "
            + ", ".join(f"{n[:40]} {b}" for n, b in top))


def run_phase(name: str, extra: list,
              clock: obs.CompileCounter) -> train.TrainRun:
    """One training run through ``train.main``; prints its numbers."""
    print(f"== {name}: {' '.join(extra)}", flush=True)
    before = clock.snapshot()
    t0 = time.perf_counter()
    run = train.main(BASE_ARGS + extra)
    wall = time.perf_counter() - t0
    if len(run.history) != ROUNDS:
        raise Fail(f"{name}: {len(run.history)} rounds of history, "
                   f"expected {ROUNDS}")
    bad = [r["round"] for r in run.history
           if not all(math.isfinite(r[k]) for k in ("loss", "grad_norm"))]
    if bad:
        raise Fail(f"{name}: non-finite loss or grad norm in rounds {bad}")
    steady = run.chunk_seconds[1:]
    per_round = (sum(s for _, s in steady) / sum(r for r, _ in steady)
                 if steady else float("nan"))
    # The round program compiles before the first chunk
    # (--donation-report), so set-up covers init plus that compile.
    setup = wall - sum(s for _, s in run.chunk_seconds)
    after = clock.snapshot()
    print(f"{name}: compile_s={after['compile_s'] - before['compile_s']:.1f} "
          f"cache_hits={after['cache_hits'] - before['cache_hits']} "
          f"cache_writes={after['cache_misses'] - before['cache_misses']} "
          f"setup_s={setup:.2f} "
          f"first_chunk_s={run.chunk_seconds[0][1]:.4f} "
          f"per_round_s={per_round:.4f} wall_s={wall:.1f} "
          f"pallas_compiled={run.interpret is False}", flush=True)
    # The compiler's plan for the executable that ran, beside what the
    # runtime's allocator saw.
    mem = run.program_bytes or {}
    print(f"{name}: round program argument_bytes={mem.get('argument')} "
          f"output_bytes={mem.get('output')} temp_bytes={mem.get('temp')}; "
          f"peak_bytes_in_use={peak_bytes_in_use()}", flush=True)
    print(f"{name}: loss " + " ".join(f"{r['loss']:.6f}"
                                      for r in run.history), flush=True)
    return run


def compare(name: str, got: train.TrainRun, ref: train.TrainRun) -> None:
    worst = 0.0
    for a, b in zip(got.history, ref.history):
        for key in ("loss", "grad_norm"):
            worst = max(worst, abs(a[key] - b[key]) / abs(b[key]))
    print(f"{name}: max relative deviation of loss and grad norm "
          f"{worst:.3e} (tolerance {RTOL:g})", flush=True)
    if not worst <= RTOL:
        raise Fail(f"{name}: histories differ by {worst:.3e} > {RTOL:g}")


def check_quartered(state, devices) -> None:
    """Every state slab is split in four equal shards, one per chip."""
    for name, arr in (("w", state.w), ("m", state.opt[0]),
                      ("v", state.opt[1])):
        shards = arr.addressable_shards
        shapes = {s.data.shape for s in shards}
        held = {s.device for s in shards}
        print(f"state.{name}: {arr.shape} in {len(shards)} shards of "
              f"{sorted(shapes)} on {len(held)} devices", flush=True)
        if (len(shards) != 4 or held != set(devices)
                or shapes != {(arr.shape[0] // 4,)}):
            raise Fail(f"state.{name} is not a quarter per device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded engine on a (4,) client mesh "
                         "and its single-device run, and nothing else")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Fail(f"no TPU: jax's first device is {dev.platform!r}")
    if resolve_interpret(None):
        raise Fail(f"Pallas interpret mode is asked for "
                   f"({INTERPRET_ENV}={os.environ.get(INTERPRET_ENV)!r})")
    if len(devices) < args.chips:
        raise Fail(f"--chips {args.chips} needs {args.chips} chips; jax "
                   f"sees {len(devices)}")
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    print(f"compile cache {cache} (max_size "
          f"{jax.config.jax_compilation_cache_max_size}): "
          f"{cache_contents(cache)}", flush=True)
    cfg = train.preset_config(ARCH, "full", LAYERS)
    print(f"model: {ARCH} published widths d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} window={cfg.window} "
          f"ssm_state={cfg.ssm_state} meta_tokens={cfg.n_meta_tokens}; "
          f"depth cut {LAYERS} of {train.get_config(ARCH).n_layers} "
          f"layers; {cfg.n_params():,} parameters", flush=True)

    clock = obs.CompileCounter()
    compiled = ["--no-interpret"]
    if args.chips == 4:
        sharded = run_phase("pallas_sharded",
                            ["--backend", "pallas_sharded", "--mesh", "4",
                             *compiled], clock)
        check_quartered(sharded.state, devices)
        sharded = sharded._replace(state=None)
        single = run_phase("pallas", ["--backend", "pallas", *compiled],
                           clock)
        compare("pallas_sharded vs pallas", sharded, single)
    else:
        fused = run_phase("pallas", ["--backend", "pallas", *compiled],
                          clock)
        fused = fused._replace(state=None)
        ref = run_phase("jnp", ["--backend", "jnp"], clock)
        compare("pallas vs jnp", fused, ref)

    print(f"compile cache after the run: {cache_contents(cache)}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Fail as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
