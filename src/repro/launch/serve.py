"""End-to-end serving driver (the paper's kind: multi-tenant diffusion
service).  Trains/loads the two relay families, precomputes the arm-quality
table for the workload, and runs the RISE LinUCB scheduler against the
Poisson request stream with pool queueing.

  PYTHONPATH=src python -m repro.launch.serve --requests 200
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def resolve_runtime_config(runtime: str, no_compress: bool, profile: bool = False):
    """RuntimeConfig for the chosen runtime.

    Both runtimes consume the transport knobs: the sequential engine
    prices inter-segment hops (and applies the measured quality delta)
    through the same :class:`HandoffTransport` the continuous runtime
    uses, so ``--no-compress`` is meaningful either way.  The batching
    knobs (buckets, linger) and the event-loop profiler apply to the
    continuous runtime only."""
    from repro.serving.runtime import RuntimeConfig

    profiler = None
    if profile:
        from repro.serving.obs.profiler import EventLoopProfiler

        profiler = EventLoopProfiler()
    return RuntimeConfig(compress_handoff=not no_compress, profiler=profiler)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--train-steps", type=int, default=1500)
    ap.add_argument("--mu", type=float, default=9.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="rise",
                    choices=["rise", "rr", "greedy", "ppo", "sac"])
    ap.add_argument("--runtime", default="continuous",
                    choices=["sequential", "continuous"],
                    help="continuous (default) = micro-batched discrete-event "
                         "runtime with compressed latent handoff and fault "
                         "injection; sequential = paper-faithful blocking loop")
    ap.add_argument("--no-compress", action="store_true",
                    help="disable int8 latent handoff compression "
                         "(hop pricing + quality delta, both runtimes)")
    ap.add_argument("--telemetry-context", action="store_true",
                    help="append live runtime telemetry (queue depth, batch "
                         "occupancy) to the LinUCB context vector")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="fraction of edge-phase requests slowed by the "
                         "straggler model")
    ap.add_argument("--straggler-factor", type=float, default=6.0,
                    help="slowdown multiplier of a straggling request")
    ap.add_argument("--straggler-mode", default="item",
                    choices=["item", "batch"],
                    help="mitigation: 'item' (default) re-runs only the "
                         "straggling samples on the twin replica "
                         "(partial-batch re-execution); 'batch' re-issues "
                         "the whole micro-batch")
    ap.add_argument("--trace-out", default="",
                    help="write the per-request relay span trace as Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing); '.jsonl' suffix emits span "
                         "records instead")
    ap.add_argument("--profile", action="store_true",
                    help="wall-clock event-loop profiler for the continuous "
                         "runtime (event counts, per-event-type handler "
                         "time, heap ops); report lands in the summary")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.telemetry_context and args.policy in ("ppo", "sac"):
        ap.error("--telemetry-context is incompatible with the offline "
                 "PPO/SAC baselines (their nets are trained on the fixed "
                 "8-dim context); rr/greedy ignore the extra dims and rise "
                 "sizes its state to the widened context")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.core import policies as pol
    from repro.serving.context import context_dim
    from repro.diffusion.train import get_or_train_families
    from repro.serving.engine import ServingEngine, SimConfig, make_requests, summarize
    from repro.serving.executor import Executor

    print("loading/training relay families...")
    fams = get_or_train_families(steps=args.train_steps, verbose=True)
    ex = Executor(fams)

    cfg = SimConfig(n_requests=args.requests, mean_interarrival=args.mu,
                    seed=args.seed, telemetry_context=args.telemetry_context,
                    straggler_prob=args.straggler_prob,
                    straggler_factor=args.straggler_factor,
                    straggler_mode=args.straggler_mode)
    reqs = make_requests(cfg)
    seeds = np.array([r.prompt_seed for r in reqs])
    print(f"precomputing quality table for {len(reqs)} requests × 11 arms...")
    qt = ex.quality_table(seeds)

    d = context_dim(args.telemetry_context)
    policy = {
        "rise": lambda: pol.RisePolicy(seed=args.seed, ctx_dim=d),
        "rr": pol.RoundRobinPolicy,
        "greedy": pol.GreedyPolicy,
        "ppo": lambda: pol.PPOPolicy(seed=args.seed),
        "sac": lambda: pol.SACPolicy(seed=args.seed),
    }[args.policy]()

    runtime_cfg = resolve_runtime_config(args.runtime, args.no_compress,
                                         profile=args.profile)
    engine = ServingEngine(policy, qt, cfg, executor=ex,
                           runtime=args.runtime, runtime_cfg=runtime_cfg)
    records = engine.run(reqs)
    summary = summarize(records)
    if engine.telemetry is not None:
        from repro.serving.obs.export import export_runtime_telemetry

        summary["runtime_telemetry"] = export_runtime_telemetry(engine.telemetry)
    if args.trace_out:
        from repro.serving.obs.export import (write_chrome_trace,
                                              write_spans_jsonl)

        writer = (write_spans_jsonl if args.trace_out.endswith(".jsonl")
                  else write_chrome_trace)
        writer(engine.tracer, args.trace_out)
        print(f"trace ({engine.tracer.coverage():.1%} of completed requests) "
              f"-> {args.trace_out}")
    if args.profile and runtime_cfg.profiler is not None:
        summary["event_loop_profile"] = runtime_cfg.profiler.report()
    print(json.dumps(summary, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)
    return summary


if __name__ == "__main__":
    main()
