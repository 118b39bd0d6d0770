"""Federated LM training driver (real execution).

Runs ADOTA-FL on an assigned architecture's REDUCED variant (CPU) or the
full config (TPU pod, same code path): clients hold Dirichlet-partitioned
shards of a synthetic token stream, each round computes client gradients,
passes them through the simulated OTA MAC, and applies the adaptive
server update.

The training state lives as a slab-resident ``SlabTrainState`` across
rounds (PR 3): params + optimizer-state slabs, sharded over the mesh
under ``--backend pallas_sharded``, with rounds dispatched as
``jax.lax.scan`` chunks of ``--scan-rounds``. Checkpoints
(``--ckpt-dir``, every ``--ckpt-every`` rounds) store the slabs raw
with a layout fingerprint; ``--resume`` continues bitwise-identically
from the latest one (all round randomness is keyed by absolute round
index, so the resumed trajectory equals the uninterrupted one).
``--uplink int8`` switches the MAC payload to the quantized uplink
(int8 codewords + per-128-block f32 scales, ~4x fewer collective bytes
per round on the sharded mesh); ``--uplink sign`` to the 1-bit signSGD
uplink (~32x, deterministic); the default f32 uplink is bitwise-
identical to the pre-pipeline code. ``--error-feedback`` carries each
transmitter's quantization residual across rounds (resident in the
slab state, checkpointed) so the quantized uplinks recover the f32
convergence trajectory; ``--downlink int8`` quantizes the per-round
model broadcast the clients see (the server keeps f32 master weights).

The compiled-mode fast path (PR 8) is on by default where it applies:
the slab state is DONATED into each scan chunk (in-place resident
update, no 2x state copy — ``--no-donate`` to disable,
``--donation-report`` to verify the executable aliases the buffers),
``--uplink sign`` rides a uint32 bit-packed wire (``--sign-pack``:
'fold' 1 bit/coord, 'planes' 2, 'int8' the PR 7 container), and
``--sr-inkernel`` moves the int8 stochastic-rounding draws into the
transmit kernel's pltpu PRNG (compiled mode only; same quantization
contract, different uniform stream).

``--client-chunk`` streams the client axis in O(chunk * d) memory
(PR 6): each chunk's gradients are computed and folded into the
running MAC partial in-kernel, so the client count is no longer bound
by host memory. ``--sample-rate`` adds per-round Bernoulli partial
participation and ``--client-weights datasize`` weights the aggregate
by Dirichlet shard size; with both off, behaviour (and bits) match the
resident path.

``--alpha`` is the TRUE channel tail index; ``--alpha-opt`` what the
server optimizer assumes (default: follows ``--alpha``) — set them
apart for mismatch experiments, or pass ``--track-alpha`` (==
``--alpha-opt auto``) to close the loop: the OTA kernel epilogues
reduce log-moment pilot statistics of the injected interference, the
resident slab state carries their EMA ``alpha_hat`` (checkpointed, so
``--resume`` continues the estimate bitwise), and the adaptive update
consumes it as a traced scalar each round.

``--preset full --layers N`` runs a published architecture at its
published widths, cut to its first N layers (the cut is printed with
the config). The Pallas kernels run compiled on a TPU and in interpret
mode elsewhere; the printed ``pallas kernels:`` line says which and
why, and ``--no-interpret`` without a TPU is an error. ``main(argv)``
returns a ``TrainRun`` (final state, per-round history read from the
device, per-chunk wall times) for callers that drive it in-process.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b \
        --preset tiny --rounds 100
    PYTHONPATH=src python -m repro.launch.train --preset 100m --rounds 300
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import time
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import repro.checkpoint as ckpt
from repro import obs
from repro.configs import ARCHS, get_config, smoke_config
from repro.core import (AdaptiveConfig, FLConfig, OTAChannelConfig,
                        UplinkConfig, donation_report, init_train_state,
                        make_slab_round_runner, make_slab_spec,
                        run_rounds_slab)
from repro.core.shard import place_slab_state
from repro.data import dirichlet_partition, token_stream
from repro.kernels.interpret import INTERPRET_ENV, resolve_interpret
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hostdev import force_host_devices, positive_int
from repro.launch.mesh import make_client_mesh
from repro.models.model import ModelConfig, build_model


def preset_config(arch: str, preset: str,
                  layers: Optional[int] = None) -> ModelConfig:
    """The model of ``--preset``; ``layers`` cuts the depth of ``full``
    (the published widths stay as they are)."""
    if layers is not None and preset != "full":
        raise ValueError(f"a depth cut applies to --preset full only, "
                         f"got --preset {preset}")
    if preset == "full":
        cfg = get_config(arch)
        if layers is None:
            return cfg
        if not 1 <= layers <= cfg.n_layers:
            raise ValueError(f"--layers must be in [1, {cfg.n_layers}] for "
                             f"{arch}, got {layers}")
        return dataclasses.replace(cfg, n_layers=layers)
    if preset == "tiny":
        return dataclasses.replace(smoke_config(arch), vocab=257)
    if preset == "100m":
        # ~100M-parameter decoder (qwen-style), the end-to-end driver size.
        return ModelConfig(
            arch=f"{arch}-100m", family="dense", n_layers=8, d_model=768,
            n_heads=12, n_kv_heads=4, d_ff=2048, vocab=8192, qk_norm=True,
            remat=False)
    raise ValueError(preset)


class TrainRun(NamedTuple):
    """What ``main`` hands back to a caller that drives it in-process."""
    state: Any                 # final SlabTrainState (device-resident)
    history: list              # one record per round, read from the device
    config: ModelConfig
    interpret: Optional[bool]  # resolved Pallas mode; None under jnp
    chunk_seconds: list        # (rounds, wall s) per dispatched scan chunk
    program_bytes: Optional[dict]  # round program's memory analysis
                                   # (--donation-report), else None


def kernel_mode(backend: str, interpret: bool) -> str:
    """One line naming where the Pallas kernels run, and why."""
    if backend == "jnp":
        return "pallas kernels: not used (--backend jnp)"
    platform = jax.default_backend()
    if not interpret:
        return (f"pallas kernels: compiled on {platform} "
                f"({jax.devices()[0].device_kind})")
    if platform == "tpu":
        return (f"pallas kernels: INTERPRET mode on tpu "
                f"({INTERPRET_ENV}={os.environ.get(INTERPRET_ENV)})")
    return f"pallas kernels: interpret mode (no TPU; jax backend {platform})"


def profile_line(profile_dir: str, n_chunks: int) -> str:
    """Where ``--profile`` wrote its trace, with the host time of each
    span of the traced chunk (``repro.obs.SPAN_RECORD``)."""
    if n_chunks < 2:
        return (f"profile: nothing traced, the run had {n_chunks} chunk(s) "
                f"and the trace is of the second")
    rec = list(obs.SPAN_RECORD)
    chunk = [(s, e) for n, s, e in rec if n == obs.CHUNK][-1]
    ms = {}
    for n, s, e in rec:
        if chunk[0] <= s and e <= chunk[1]:
            ms[n] = ms.get(n, 0.0) + (e - s) * 1e-6
    return (f"profile: chunk 2 traced to {profile_dir}; host spans (ms): "
            + ", ".join(f"{n} {ms[n]:.3f}" for n in obs.SPANS if n in ms))


def main(argv: Optional[Sequence[str]] = None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen3-14b")
    ap.add_argument("--preset", choices=["tiny", "100m", "full"],
                    default="tiny")
    ap.add_argument("--layers", type=positive_int, default=None,
                    help="depth cut for --preset full: keep the first N "
                         "layers at the published widths")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--client-chunk", type=int, default=None,
                    help="stream the client axis in chunks of this many "
                         "rows (per device under pallas_sharded): peak "
                         "memory O(chunk * d) instead of O(N * d); must "
                         "divide the per-device client count. Default: "
                         "resident (all clients at once)")
    ap.add_argument("--sample-rate", type=float, default=1.0,
                    help="per-round Bernoulli participation probability; "
                         "< 1 samples a client subset each round (keyed "
                         "off the round key, identical on all backends)")
    ap.add_argument("--client-weights", default="uniform",
                    choices=["uniform", "datasize"],
                    help="per-client aggregation weights: 'uniform' "
                         "(1/N, default) or 'datasize' (proportional to "
                         "the client's Dirichlet shard size)")
    ap.add_argument("--batch", type=int, default=2, help="per-client batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="adam_ota",
                    choices=["adam_ota", "adagrad_ota", "amsgrad_ota",
                             "yogi_ota", "fedavgm", "fedavg"])
    ap.add_argument("--backend", default="jnp",
                    choices=["jnp", "pallas", "pallas_sharded"],
                    help="round-step backend: per-leaf jnp tree.map, the "
                         "fused Pallas slab engine (2 kernel launches/"
                         "round), or the mesh-distributed slab engine "
                         "(2 launches per DEVICE + cross-client psum)")
    ap.add_argument("--mesh", default=None,
                    help="client-mesh shape for --backend pallas_sharded, "
                         "comma-separated (e.g. '2' or '4,2', default 2); "
                         "the client count must be divisible by its product")
    ap.add_argument("--uplink", default="f32",
                    choices=["f32", "int8", "sign"],
                    help="MAC payload format: f32 is the analog uplink "
                         "(today's behaviour, bitwise); int8 quantizes each "
                         "transmitter's faded partial sum to int8 + "
                         "per-128-block f32 scales (stochastic rounding) — "
                         "~4x fewer collective bytes on the sharded MAC; "
                         "sign is the 1-bit signSGD payload with blockwise "
                         "mean-magnitude scales (deterministic, ~32x)")
    ap.add_argument("--downlink", default="f32", choices=["f32", "int8"],
                    help="model-broadcast format: f32 (default, bitwise) "
                         "or int8 (per-128-block scales + stochastic "
                         "rounding, ~4x fewer broadcast bytes; clients see "
                         "the reconstruction, the server keeps f32 master "
                         "weights)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="carry each transmitter's quantization residual "
                         "across rounds and add it back before the next "
                         "quantize (needs --uplink int8 or sign); resident "
                         "in the slab state and checkpointed, so --resume "
                         "continues the residual bitwise")
    ap.add_argument("--no-interpret", action="store_true",
                    help="compile the Pallas kernels (needs a TPU) instead of the "
                         "platform default (auto: compiled on TPU, "
                         "interpret mode elsewhere; see also the "
                         "REPRO_PALLAS_INTERPRET env var)")
    ap.add_argument("--sign-pack", default="fold",
                    choices=["fold", "planes", "int8"],
                    help="wire container for --uplink sign: 'fold' packs "
                         "the signs into uint32 bitplanes at 1 bit/coord "
                         "(exact zeros fold to +1, all-zero blocks keep "
                         "scale 0 so the padded tail survives), 'planes' "
                         "keeps a separate nonzero-mask plane (2 bits/"
                         "coord, zeros exact), 'int8' is the PR 7 "
                         "byte-per-coord container")
    ap.add_argument("--sr-inkernel", action="store_true",
                    help="draw the int8 stochastic-rounding uniforms "
                         "inside the Pallas transmit kernel (pltpu PRNG) "
                         "instead of streaming a host-drawn f32 row "
                         "through HBM; compiled mode only (ignored under "
                         "interpret / --backend jnp), same one-block-"
                         "scale quantization contract, different uniform "
                         "stream — not bitwise vs the host-drawn path")
    ap.add_argument("--comm-buckets", type=int, default=1,
                    help="split the sharded MAC collective into this many "
                         "slab buckets, interleaved with the per-bucket "
                         "transmit epilogue (pallas_sharded only; the "
                         "overlap engine, tolerance-tier vs the default); "
                         "1 (default) keeps the single-collective graph "
                         "bitwise")
    ap.add_argument("--double-buffer", action="store_true",
                    help="two-slot pipeline for the streamed client scan: "
                         "chunk c's gradients are computed while chunk "
                         "c-1's slot folds into the accumulators (needs "
                         "--client-chunk; tolerance-tier reassociation "
                         "of the per-chunk fold)")
    ap.add_argument("--ckpt-async", action="store_true",
                    help="write checkpoints on a background thread: the "
                         "host snapshot is taken synchronously (safe "
                         "under donation), the npz encode + atomic "
                         "rename overlap training; files are bitwise "
                         "identical to the blocking path and all writes "
                         "are joined at loop exit")
    ap.add_argument("--no-donate", action="store_true",
                    help="keep a second resident copy of the slab state "
                         "across the scan dispatch instead of donating "
                         "the input slabs to the compiled runner "
                         "(donation is safe here: run_rounds_slab "
                         "threads the state linearly)")
    ap.add_argument("--donation-report", action="store_true",
                    help="before training, lower+compile the round runner "
                         "and print how many donated input bytes the "
                         "executable actually aliases to outputs "
                         "(verifies the slabs are updated in place, not "
                         "copied)")
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--alpha", type=float, default=1.5,
                    help="TRUE tail index of the channel's alpha-stable "
                         "interference (what the simulator injects)")
    ap.add_argument("--alpha-opt", default=None,
                    help="tail index the server OPTIMIZER assumes: a float "
                         "(fixed assumption — set != --alpha for mismatch "
                         "experiments) or 'auto' (closed-loop online "
                         "estimation from the fused pilot statistics). "
                         "Default: 'auto' under --track-alpha, else "
                         "--alpha (matched, the old conflated behaviour)")
    ap.add_argument("--track-alpha", action="store_true",
                    help="shorthand for --alpha-opt auto: estimate the "
                         "interference tail index online (log-moment "
                         "stats fused into the OTA kernel epilogue, EMA "
                         "resident in the slab state, checkpointed) and "
                         "feed it back into the adaptive update")
    ap.add_argument("--xi-scale", type=float, default=0.05)
    ap.add_argument("--dir", type=float, default=0.5,
                    help="Dirichlet concentration (data heterogeneity)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(bitwise-identical continuation: round keys are "
                         "derived from the absolute round index)")
    ap.add_argument("--scan-rounds", type=int, default=8,
                    help="rounds fused into one jax.lax.scan dispatch over "
                         "the resident slab state (clipped to log/ckpt "
                         "boundaries)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--history-out", default=None)
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="write a profiler trace of the second scan chunk "
                         "(the first after the one that compiles) to DIR; "
                         "bench/inspect_trace.py DIR reads it")
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir")
    if args.scan_rounds < 1:
        ap.error("--scan-rounds must be >= 1")

    # Resolve the optimizer's assumed alpha: --track-alpha and
    # --alpha-opt auto are synonyms; a bare float pins the assumption
    # (mismatch scenarios); unset follows the true channel alpha.
    if args.alpha_opt is None:
        alpha_opt = "auto" if args.track_alpha else args.alpha
    elif args.alpha_opt == "auto":
        alpha_opt = "auto"
    else:
        try:
            alpha_opt = float(args.alpha_opt)
        except ValueError:
            ap.error(f"--alpha-opt must be a float or 'auto', "
                     f"got {args.alpha_opt!r}")
        if args.track_alpha:
            ap.error("--track-alpha conflicts with a fixed --alpha-opt "
                     f"{alpha_opt}; drop one of the two")
    track = alpha_opt == "auto"

    mesh = None
    if args.mesh is not None and args.backend != "pallas_sharded":
        ap.error(f"--mesh only applies to --backend pallas_sharded "
                 f"(got --backend {args.backend}); it would be silently "
                 f"ignored on a single-device backend")
    if args.backend == "pallas_sharded":
        try:
            mesh_shape = tuple(int(x) for x in (args.mesh or "2").split(","))
            if not mesh_shape or any(s < 1 for s in mesh_shape):
                raise ValueError
        except ValueError:
            ap.error(f"--mesh must be comma-separated positive ints "
                     f"(e.g. '2' or '4,2'), got {args.mesh!r}")
        # A CPU host exposes one device; force enough host devices for
        # the mesh BEFORE jax initialises its backend (first jax array
        # op locks the count).
        force_host_devices(math.prod(mesh_shape))

    try:
        cfg = preset_config(args.arch, args.preset, args.layers)
    except ValueError as e:
        ap.error(str(e))
    model = build_model(cfg)
    cut = (f" (depth cut: {cfg.n_layers} of "
           f"{get_config(args.arch).n_layers} layers)"
           if args.layers is not None else "")
    print(f"arch={cfg.arch} layers={cfg.n_layers}{cut} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"d_ff={cfg.d_ff} params={cfg.n_params()/1e6:.1f}M "
          f"vocab={cfg.vocab} clients={args.clients} "
          f"alpha={args.alpha} alpha_opt={alpha_opt}")

    # Client corpora: one shared stream, Dirichlet-partitioned by "domain"
    # id so clients see different mixtures (non-iid).
    toks = token_stream(2_000_000, vocab=cfg.vocab, seed=args.seed)
    n_windows = (len(toks) - args.seq - 1) // args.seq
    starts_all = np.arange(n_windows) * args.seq
    domain = (starts_all // (len(toks) // 16)).astype(np.int64)  # 16 domains
    parts = dirichlet_partition(domain, args.clients, args.dir,
                                seed=args.seed, min_per_client=args.batch)

    def batch_fn(t, key):
        # Keyed by the ABSOLUTE round index (not by call count): a
        # resumed process must draw the same batches for round t as the
        # uninterrupted one, or --resume could not be bitwise-identical.
        rng = np.random.default_rng((args.seed, t))
        out = np.empty((args.clients, args.batch, args.seq), np.int32)
        for c, p in enumerate(parts):
            pick = rng.choice(p, size=args.batch, replace=len(p) < args.batch)
            for j, w in enumerate(pick):
                s = starts_all[w]
                out[c, j] = toks[s:s + args.seq]
        return {"tokens": jnp.asarray(out)}

    # None = auto-select from the platform (compiled on TPU only);
    # --no-interpret pins compiled mode explicitly. A compiled run needs
    # a TPU: without one it is an error, never a silent CPU run.
    interpret = False if args.no_interpret else None
    resolved = resolve_interpret(interpret)
    if (args.backend != "jnp" and not resolved
            and jax.default_backend() != "tpu"):
        ap.error(f"compiled Pallas kernels need a TPU, but jax's backend "
                 f"is {jax.default_backend()!r}; drop --no-interpret (and "
                 f"{INTERPRET_ENV}=0) to run them in interpret mode")
    print(kernel_mode(args.backend, resolved))
    if args.error_feedback and args.uplink == "f32":
        ap.error("--error-feedback needs a quantized uplink "
                 "(--uplink int8 or sign); the f32 payload has no residual")
    if args.sr_inkernel and args.uplink != "int8":
        ap.error("--sr-inkernel applies to the stochastically rounded "
                 f"int8 uplink only (got --uplink {args.uplink})")
    if args.comm_buckets < 1:
        ap.error("--comm-buckets must be >= 1")
    if args.comm_buckets > 1 and args.backend != "pallas_sharded":
        ap.error("--comm-buckets > 1 buckets the sharded MAC collective; "
                 f"it needs --backend pallas_sharded (got {args.backend})")
    if args.double_buffer and args.client_chunk is None:
        ap.error("--double-buffer pipelines the streamed client scan; "
                 "it needs --client-chunk")
    ch = OTAChannelConfig(alpha=args.alpha, xi_scale=args.xi_scale,
                          backend=args.backend, interpret=interpret,
                          uplink=UplinkConfig(
                              mode=args.uplink,
                              error_feedback=args.error_feedback,
                              sign_pack=args.sign_pack,
                              sr_inkernel=args.sr_inkernel),
                          downlink=args.downlink,
                          comm_buckets=args.comm_buckets)
    ad = AdaptiveConfig(optimizer=args.optimizer, lr=args.lr,
                        alpha=alpha_opt, beta2=0.3, backend=args.backend,
                        interpret=interpret)
    n_shards = 1
    if args.backend == "pallas_sharded":
        try:
            mesh = make_client_mesh(mesh_shape)
        except ValueError as e:
            ap.error(str(e))
        n_shards = math.prod(mesh_shape)
        print(f"client mesh {dict(mesh.shape)} "
              f"({len(jax.devices())} devices visible)")
    weights = None
    if args.client_weights == "datasize":
        weights = tuple(float(len(p)) for p in parts)
    fl = FLConfig(n_clients=args.clients, client_chunk=args.client_chunk,
                  sample_rate=args.sample_rate, client_weights=weights,
                  double_buffer=args.double_buffer)
    # The driver threads the state linearly through run_rounds_slab, so
    # donating the slabs is safe by construction: each chunk's output
    # state is the only live reference to the next chunk's input.
    run_chunk = make_slab_round_runner(lambda p, b: model.loss_fn(p, b), ch,
                                       ad, fl, mesh=mesh,
                                       donate=not args.no_donate)
    enable_compile_cache()
    init_key = jax.random.key(args.seed)
    spec = make_slab_spec(jax.eval_shape(model.init, init_key),
                          shards=n_shards)
    # One program draws the parameters and packs them into slabs; run
    # eagerly, every op of the init would compile a program of its own.
    # Pytrees exist only at boundaries from here on.
    state = jax.jit(lambda k: init_train_state(
        ad, model.init(k), spec=spec,
        error_feedback=args.error_feedback))(init_key)

    start_round = 0
    if args.resume:
        latest = ckpt.latest_round(args.ckpt_dir)
        if latest is None:
            print(f"no checkpoint under {args.ckpt_dir}; starting fresh")
        else:
            state, _ = ckpt.load_slab_state(latest, spec)
            start_round = int(state.step)
            print(f"resumed from {latest} at round {start_round}")
            # Reconcile the EF slab with this run's flags: a pre-EF (or
            # EF-off) checkpoint resumed WITH --error-feedback starts
            # the residual loop fresh (zeros); an EF checkpoint resumed
            # WITHOUT the flag drops the carried residual.
            if args.error_feedback and state.ef is None:
                print("checkpoint carries no error-feedback residual; "
                      "starting the EF loop from zeros")
                state = dataclasses.replace(
                    state, ef=jnp.zeros((spec.shards, spec.padded),
                                        jnp.float32))
            elif not args.error_feedback and state.ef is not None:
                print("checkpoint carries an error-feedback residual but "
                      "--error-feedback is off; dropping it")
                state = dataclasses.replace(state, ef=None)
    if mesh is not None:
        # Enter the runner with the shardings it returns the state in,
        # so that later chunks reuse the first chunk's program.
        state = place_slab_state(state, mesh)

    base_key = jax.random.key(args.seed + 1)

    program_bytes = None
    if args.donation_report and start_round < args.rounds:
        r = min(args.scan_rounds, args.rounds - start_round)
        ks = jnp.stack([jax.random.fold_in(base_key, start_round + i)
                        for i in range(r)])
        bs = [batch_fn(start_round + i, None) for i in range(r)]
        ex = jax.tree.map(lambda *xs: jnp.stack(xs), *bs)
        rep = donation_report(run_chunk, state, ks, ex)
        program_bytes = rep["program_bytes"]
        if rep["supported"]:
            print(f"donation: {rep['aliased_bytes']:,} / "
                  f"{rep['donated_bytes']:,} state bytes aliased "
                  f"in-place ({len(rep['aliased_pairs'] or [])} buffers)")
            if program_bytes:
                print("round program memory: " + ", ".join(
                    f"{k} {v:,}" for k, v in program_bytes.items())
                    + " bytes")
        else:
            print("donation: memory analysis not exposed on this backend; "
                  "aliasing unverified")

    t0 = time.time()
    chunk_seconds = []
    t_chunk = [t0, start_round]
    compiles = obs.CompileCounter()
    built = [0]

    def chunk_hook(t, st, history):
        # The history of the chunk was read from the device before this
        # hook runs, so the wall time covers the chunk's work.
        now = time.time()
        chunk_seconds.append((t - t_chunk[1], now - t_chunk[0]))
        # A chunk after the first that builds a program (compiles it or
        # loads it from the cache) is named, so a recompile is seen.
        if len(chunk_seconds) > 1 and compiles.programs() > built[0]:
            print(f"chunk {len(chunk_seconds)} (rounds {t_chunk[1] + 1}-{t})"
                  f" built {compiles.programs() - built[0]} program(s): "
                  f"{compiles.compiles} compiled, {compiles.hits} from the "
                  f"compile cache since the loop started", flush=True)
        built[0] = compiles.programs()
        t_chunk[:] = [now, t]
        # run_rounds_slab clips chunks to the align periods, so every
        # log/checkpoint multiple lands exactly on a chunk boundary.
        if args.log_every and t % args.log_every == 0:
            rec = history[-1]
            dt = time.time() - t0
            a_col = (f"  a^ {rec['alpha_hat']:.3f}" if track else "")
            print(f"round {t:5d}  loss {rec['loss']:.4f}  "
                  f"|g| {rec['grad_norm']:.3e}{a_col}  "
                  f"({dt / (t - start_round):.2f}s/round)", flush=True)
        if args.ckpt_dir and args.ckpt_every and t % args.ckpt_every == 0:
            ckpt.save_slab_state(os.path.join(args.ckpt_dir,
                                              f"round_{t}.npz"), st,
                                 blocking=not args.ckpt_async)

    with compiles:
        state, history = run_rounds_slab(
            run_chunk, state, None, batch_fn, args.rounds,
            chunk=args.scan_rounds,
            key_fn=lambda t: jax.random.fold_in(base_key, t),
            start_round=start_round, chunk_hook=chunk_hook,
            align=(args.log_every, args.ckpt_every if args.ckpt_dir else 0),
            profile_dir=args.profile)
    if args.profile:
        print(profile_line(args.profile, len(chunk_seconds)))
    if args.ckpt_async:
        ckpt.wait_for_async_saves()
    if args.history_out:
        os.makedirs(os.path.dirname(os.path.abspath(args.history_out)),
                    exist_ok=True)
        with open(args.history_out, "w") as f:
            json.dump(history, f)
    if history:
        a_col = (f"  alpha_hat {history[-1]['alpha_hat']:.4f} "
                 f"(true {args.alpha})" if track else "")
        print(f"done: final loss {history[-1]['loss']:.4f} "
              f"(started {history[0]['loss']:.4f}){a_col}")
    else:
        print(f"done: nothing to do (resumed at round {start_round} "
              f">= --rounds {args.rounds})")
    return TrainRun(state, history, cfg,
                    None if args.backend == "jnp" else resolved,
                    chunk_seconds, program_bytes)


if __name__ == "__main__":
    main()
