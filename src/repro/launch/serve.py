"""Serving launcher: continuous-batching generation demo.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
      --requests 8 --engine continuous [--pim fast]

``--engine continuous`` (default) drives the slot-based scheduler on a
mixed-length request trace and reports decode-step utilization next to
throughput; ``--engine lockstep`` runs the fixed-batch reference engine;
``--engine paged`` serves the trace from a paged KV block pool
(``--block-size``/``--blocks``, see ``repro.serve.PagedServeEngine``)
and reports pool occupancy, prefix-sharing hits, and evictions.
``--pim fast`` compiles the params with the per-site architecture
compiler (``repro.models.pim_compile``, on a random calibration batch)
and routes every weight-static projection through the centered int8 path
(Eq. 1 on the MXU); ``--pim exact`` runs the bit-exact accelerator
simulation, ``--pim int8`` the ideal 8b-quantized reference — see
``benchmarks/serve_pim.py`` for the throughput comparison.
``--pim-slicing adaptive`` runs the paper's Algorithm 1 per projection
site (printing the slice-count histogram and per-site table);
``--pim-slicing 4,2,2`` pins every site. ``--device-corner 3sigma``
(with ``--pim exact``) serves on a nonideal ReRAM die — the
``repro.core.backends`` device model with program noise, drift,
stuck-at faults, and IR drop at the named corner. See
``benchmarks/compile_report.py`` for the Titanium-Law pricing of the
compiled plan.

The first line printed names the platform, device kind and device count,
and the backend each kernel op resolved to. ``main`` runs
``parse_args`` -> ``setup`` -> ``make_engine`` -> ``run``;
``chip_smoke.py`` drives the same steps on the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Any

import jax
import numpy as np

from repro import configs, obs
from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models import pim
from repro.models import transformer as T
from repro.serve import (
    ContinuousServeEngine,
    PagedServeEngine,
    Request,
    ServeEngine,
)


def build_trace(n: int, *, prompt_len: int, steps: int, vocab: int,
                seed: int = 1) -> list[Request]:
    """Mixed-length trace: prompt lengths in [prompt_len/2, prompt_len],
    output lengths in [steps/4, steps] — the raggedness a lockstep batch
    pads away."""
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(n):
        plen = int(rng.integers(max(1, prompt_len // 2), prompt_len + 1))
        reqs.append(Request(
            uid=uid,
            prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(max(1, steps // 4), steps + 1))))
    return reqs


KERNEL_OPS = ("centered_int8_matmul", "fused_crossbar",
              "fused_spec_crossbar")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at a fixed place.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout (git-ignored): a fixed path, because a
    cache whose path moves is never hit. Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_report(kernel_backend: str | None = None) -> dict:
    """The devices this process runs on and the backend each kernel op
    resolves to for ``kernel_backend`` (``'python'`` — an exact-path
    choice of the reference loop — resolves like ``'auto'``)."""
    devs = jax.devices()
    request = None if kernel_backend == "python" else kernel_backend
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "kernels": {op: kops.resolve_backend(op, request)
                        for op in KERNEL_OPS}}


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", choices=("continuous", "lockstep", "paged"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=8,
                    help="trace length (continuous) / batch size (lockstep)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged engine: KV tokens per pool block")
    ap.add_argument("--blocks", type=int, default=None,
                    help="paged engine: pool size in blocks (default "
                         "slots * max_len/block_size — no memory pressure; "
                         "smaller values exercise queue-until-blocks-free "
                         "and eviction-by-recompute)")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics snapshot here as JSON "
                         "(Prometheus text exposition included; "
                         "repro.obs.export.write_metrics)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace (Perfetto-loadable) span "
                         "log of the run here")
    ap.add_argument("--profile-dir", default=None,
                    help="wrap the run in jax.profiler start/stop_trace "
                         "writing the device profile here")
    ap.add_argument("--pim", choices=("off", "fast", "exact", "int8"),
                    default="off")
    ap.add_argument("--pim-slicing", default=None,
                    help="'adaptive' (Algorithm 1 per projection site) or "
                         "a comma tuple like '4,2,2' pinning every site")
    ap.add_argument("--device-corner", default=None,
                    choices=("nominal", "1sigma", "3sigma"),
                    help="run --pim exact on a nonideal ReRAM die "
                         "(repro.core.backends.NonidealSim): conductance "
                         "program noise, retention drift, stuck-at fault "
                         "maps, IR drop at the named corner. 'nominal' is "
                         "the all-zero corner (bit-exact with the ideal "
                         "sim — the zero-corner contract)")
    ap.add_argument("--device-seed", type=int, default=0,
                    help="die seed for --device-corner fault/noise maps")
    ap.add_argument("--kernel-backend", default=None,
                    choices=("auto", "xla", "interpret", "pallas-tpu",
                             "python"),
                    help="repro.kernels.ops registry backend for the PIM "
                         "kernels (fused exact datapath / fast matmul); "
                         "'auto' = pallas-tpu on TPU, XLA ref elsewhere. "
                         "REPRO_KERNEL_BACKEND overrides at dispatch time")
    args = ap.parse_args(argv)
    if args.engine == "lockstep" and (args.metrics_out or args.trace_out
                                      or args.profile_dir):
        ap.error("--metrics-out/--trace-out/--profile-dir instrument the "
                 "continuous/paged scheduler loops; the lockstep "
                 "reference engine has no request lifecycle to trace")
    if args.kernel_backend is not None and args.pim == "off":
        ap.error("--kernel-backend requires --pim fast|exact|int8")
    if args.device_corner is not None and args.pim != "exact":
        ap.error("--device-corner requires --pim exact (only the "
                 "bit-exact accelerator simulation models the analog "
                 "array)")
    if args.pim_slicing is not None and args.pim == "off":
        ap.error("--pim-slicing requires --pim fast|exact|int8 "
                 "(the float path has no compile step)")
    return args


@dataclasses.dataclass
class Setup:
    """What one serve run is built from: config, weights, PIM plans."""
    cfg: ArchConfig
    params: Any
    plans: Any = None


def setup(args: argparse.Namespace) -> Setup:
    """Config from ``args``, random weights from a fixed seed, and the
    compiled PIM plans (on a seeded random calibration batch)."""
    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.pim != cfg.pim_mode:
        cfg = dataclasses.replace(cfg, pim_mode=args.pim)
    if args.kernel_backend is not None:
        cfg = dataclasses.replace(cfg, pim_kernel_backend=args.kernel_backend)
    if args.device_corner is not None:
        cfg = dataclasses.replace(cfg, pim_crossbar_backend="nonideal",
                                  pim_device_corner=args.device_corner,
                                  pim_device_seed=args.device_seed)
        print(f"device corner: {args.device_corner} "
              f"(die seed {args.device_seed}, nonideal ReRAM array)")
    if args.pim_slicing is not None:
        slicing = args.pim_slicing if args.pim_slicing == "adaptive" \
            else tuple(int(b) for b in args.pim_slicing.split(","))
        cfg = dataclasses.replace(cfg, pim_weight_slicing=slicing)
    params, _ = T.init_params(cfg, jax.random.key(0))

    plans = None
    if cfg.pim_mode != "off":
        calib = np.asarray(jax.random.randint(
            jax.random.key(7), (2, max(args.prompt_len, 4)), 0,
            cfg.vocab_size))
        t0 = time.monotonic()
        compiled = pim.compile_pim_params(params, cfg, calib)
        plans = compiled.plans
        print(f"compiled pim plans ({cfg.pim_mode}, "
              f"slicing={cfg.pim_weight_slicing}) in "
              f"{time.monotonic() - t0:.2f}s: {len(compiled.sites)} sites, "
              f"slice histogram {compiled.slice_histogram()}")
        if cfg.pim_weight_slicing == "adaptive":
            for sp in compiled.sites:
                err = "-" if sp.error is None else f"{sp.error:.4f}"
                print(f"  {sp.site:36s} {'-'.join(map(str, sp.slicing)):16s}"
                      f" err={err}")
    return Setup(cfg=cfg, params=params, plans=plans)


def max_len_for(args: argparse.Namespace) -> int:
    max_len = args.prompt_len + args.steps + 1
    if args.engine == "paged":
        max_len = -(-max_len // args.block_size) * args.block_size
    return max_len


def make_engine(args: argparse.Namespace, s: Setup):
    """The continuous or paged engine ``args`` names, with telemetry."""
    tel = obs.ServeTelemetry(engine=args.engine,
                             tracing=args.trace_out is not None,
                             profile_dir=args.profile_dir)
    if args.engine == "paged":
        return PagedServeEngine(s.cfg, s.params, n_slots=args.slots,
                                max_len=max_len_for(args),
                                prefill_chunk=args.prefill_chunk,
                                block_size=args.block_size,
                                n_blocks=args.blocks, plans=s.plans,
                                telemetry=tel)
    return ContinuousServeEngine(s.cfg, s.params, n_slots=args.slots,
                                 max_len=max_len_for(args),
                                 prefill_chunk=args.prefill_chunk,
                                 plans=s.plans, telemetry=tel)


def run(args: argparse.Namespace, s: Setup, eng) -> list:
    """Serve the seeded trace on ``eng`` and print the run's report."""
    cfg, tel = s.cfg, eng.tel
    trace = build_trace(args.requests, prompt_len=args.prompt_len,
                        steps=args.steps, vocab=cfg.vocab_size)
    for i, r in enumerate(trace):
        trace[i] = dataclasses.replace(r, temperature=args.temperature)
    t0 = time.monotonic()
    with tel.profile():
        outs = eng.run(trace)
    dt = time.monotonic() - t0
    total = sum(len(o.tokens) for o in outs)
    st = eng.stats
    dev = jax.devices()[0]
    print(f"{cfg.name} {args.engine}: {len(outs)} requests, {total} tokens in "
          f"{dt:.2f}s on {dev.platform} {dev.device_kind}, compiles "
          f"included ({total / dt:.1f} tok/s)")
    print(f"decode utilization {st.decode_utilization:.2f} tokens/step over "
          f"{args.slots} slots ({st.decode_steps} decode steps, "
          f"{st.prefill_chunks} prefill chunks)")
    if args.engine == "paged":
        print(f"block pool: peak {st.peak_blocks_in_use}/"
              f"{eng.alloc.n_blocks} blocks of {args.block_size}, "
              f"{st.prefix_block_hits} prefix hits, {st.evictions} "
              f"evictions, {st.admission_waits} admission waits")
    tel.record_stats(st)
    if cfg.pim_mode == "exact":
        converts, tokens, fails = (
            int(tel.registry.counter(f"repro_pim_{k}_total", "",
                                     ("engine",)).get(engine=tel.engine))
            for k in ("adc_converts", "decode_tokens", "spec_failures"))
        print(f"pim exact decode: {converts} converts over {tokens} tokens "
              f"({converts / max(tokens, 1):.1f} converts/token, {fails} "
              f"speculation failures)")
    if args.metrics_out:
        obs.write_metrics(tel.registry, args.metrics_out,
                          config={"arch": cfg.name, "engine": args.engine,
                                  "pim_mode": cfg.pim_mode,
                                  "requests": args.requests,
                                  "slots": args.slots},
                          stats=st.snapshot())
        print(f"metrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        tel.tracer.write(args.trace_out)
        print(f"chrome trace ({len(tel.tracer.events())} events) -> "
              f"{args.trace_out}")
    if args.profile_dir:
        print(f"jax profiler trace -> {args.profile_dir}")
    print("first outputs:", {o.uid: o.tokens[:8].tolist() for o in outs[:2]})
    return outs


def main(argv: list[str] | None = None) -> list:
    args = parse_args(argv)
    use_compile_cache()
    rep = device_report(args.kernel_backend)
    print(f"device: {rep['platform']} {rep['kind']} x{rep['count']}; "
          f"kernels: " + ", ".join(f"{op} -> {be}"
                                   for op, be in rep["kernels"].items()))
    s = setup(args)
    if args.engine == "lockstep":
        eng = ServeEngine(s.cfg, s.params, max_len=max_len_for(args),
                          temperature=args.temperature, plans=s.plans)
        prompts = np.asarray(jax.random.randint(
            jax.random.key(1), (args.requests, args.prompt_len), 0,
            s.cfg.vocab_size))
        t0 = time.monotonic()
        res = eng.generate(prompts, steps=args.steps)
        dt = time.monotonic() - t0
        print(f"{s.cfg.name} lockstep: generated {res.tokens.shape} in "
              f"{dt:.2f}s ({args.requests * args.steps / dt:.1f} tok/s)")
        print(res.tokens[:2])
        return []
    return run(args, s, make_engine(args, s))


if __name__ == "__main__":
    main()
