"""Flow-matching sampling service — a thin shell over the serving engine.

Requests go through :class:`repro.serving.ServingEngine` (bucketed
continuous batching, compile-cache warmup, LRU cond cache, sharded
inference); backbone and solver are registry names, so any registered
combination serves.  Compile time and steady-state throughput are reported
*separately* — the warmup pass pre-traces the bucket grid and is excluded
from the serve timing.

  PYTHONPATH=src python -m repro.launch.serve --arch flux_dit --reduced \\
      --sde ode --requests 16 --set flow.num_steps=8

  # 4-way sharded serving on faked CPU devices (bit-identical per request
  # to single-device):
  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
      python -m repro.launch.serve --reduced --requests 32 \\
      --set dist.data_parallel=4
"""
from __future__ import annotations

import json
import time

import jax
import numpy as np

from repro.api import Experiment, FlowSampler  # noqa: F401 (re-export)
from repro.api.experiment import default_cli_config
from repro.config import replace
from repro.launch.compile_cache import enable_compile_cache


def serve_profile():
    """Serving defaults: deterministic ODE solver, small latent geometry."""
    cfg = default_cli_config()
    return replace(cfg, flow=replace(cfg.flow, sde_type="ode", eta=0.3))


def main(argv=None) -> None:
    enable_compile_cache()
    ap = Experiment.cli_parser("Flow-Factory sampling service")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--bucket", default="", metavar="B1,B2,...",
                    help="comma-separated batch bucket tiers "
                         "(default: powers of two up to --max-batch)")
    ap.add_argument("--deadline-ms", type=float, default=5.0,
                    help="max wait before a partial bucket is flushed")
    ap.add_argument("--step-tiers", default="", metavar="S1,S2,...",
                    help="admitted num_steps quality tiers (warmed and "
                         "enforced at submit; default: flow.num_steps only)")
    ap.add_argument("--stats-json", default="", metavar="PATH",
                    help="write the engine's JSON stats/health snapshot "
                         "to PATH after serving ('-' prints to stdout)")
    args = ap.parse_args(argv)
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if args.max_batch < 1:
        ap.error("--max-batch must be >= 1")
    try:
        buckets = ([int(b) for b in args.bucket.split(",") if b]
                   if args.bucket else None)
        if buckets and any(b < 1 for b in buckets):
            raise ValueError(f"bucket sizes must be >= 1, got {buckets}")
    except ValueError as e:
        ap.error(f"--bucket: {e}")
    try:
        step_tiers = ([int(s) for s in args.step_tiers.split(",") if s]
                      if args.step_tiers else None)
        if step_tiers and any(s < 1 for s in step_tiers):
            raise ValueError(f"step tiers must be >= 1, got {step_tiers}")
    except ValueError as e:
        ap.error(f"--step-tiers: {e}")
    exp = Experiment.from_args(args, base=serve_profile())

    from repro.data import synthetic_prompts
    prompts = synthetic_prompts(args.requests)
    key = jax.random.PRNGKey(exp.cfg.seed)
    engine = exp.build_engine(key, max_batch=args.max_batch, buckets=buckets,
                              step_tiers=step_tiers,
                              deadline_s=args.deadline_ms / 1e3)

    # warmup: pre-trace the bucket grid and prime the cond encoder; both are
    # reported separately so the serve timing below is pure steady state
    # (the historical report timed a warm jit cache over a ~0s region and
    # printed "inf req/s")
    t0 = time.perf_counter()
    report = engine.warmup()
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.encode(prompts)               # encoder compile + cond-cache fill
    enc_s = time.perf_counter() - t0
    grid = " ".join(f"{k}={v:.2f}s" for k, v in sorted(report.items()))
    print(f"warmup: traced {len(report)} bucket shapes in {warm_s:.2f}s "
          f"({grid}); cond encode+cache {enc_s:.2f}s")

    t0 = time.perf_counter()
    latents = engine.serve(prompts, key)
    jax.block_until_ready(latents)
    dt = max(time.perf_counter() - t0, 1e-9)
    s = engine.stats
    # one transfer, reused for the rms report and the finite check —
    # float(jnp.sqrt(...)) here would force a second device round-trip
    # after block_until_ready (jaxlint R002)
    lat = np.asarray(latents)
    print(f"steady-state: served {args.requests} requests in {dt:.3f}s "
          f"({args.requests/dt:.1f} req/s); latents {latents.shape}, "
          f"rms={float(np.sqrt((lat**2).mean())):.3f}")
    print(f"engine: buckets={s['buckets']} step_tiers={s['step_tiers']} "
          f"dp={s['data_parallel']} dispatches={s['dispatches']} "
          f"padded_lanes={s['padded_lanes']} "
          f"cold_dispatches={s['cold_dispatches']} "
          f"cond_cache={s['cond_cache']}")
    if args.stats_json:
        payload = json.dumps(s, indent=2, sort_keys=True)
        if args.stats_json == "-":
            print(payload)
        else:
            with open(args.stats_json, "w") as f:
                f.write(payload + "\n")
            print(f"stats: wrote JSON snapshot to {args.stats_json}")
    assert s["cold_dispatches"] == 0, "steady-state serve hit a compile"
    assert np.isfinite(lat).all()


if __name__ == "__main__":
    main()
