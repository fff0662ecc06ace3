"""Cascade frontier benchmark: 3-hop L→M→S relay programs vs the paper's
2-hop action space.

For each family the sweep generates latents with the real JAX models for
every 2-hop relay arm (s ∈ RELAY_STEPS), the standalone small model, and
the shipped 3-hop cascade set (``repro.serving.arms.DEFAULT_CASCADES``),
scoring quality with the oracle metrics and pricing latency with the
calibrated per-segment testbed model (``latency.program_latency``, no
jitter).  A cascade "lands on the frontier" when no 2-hop arm is both
faster and at least as good — the mid stage buys large-model-like quality
at mid-stage step cost, so L→M→S points should interpolate the gap
between adjacent 2-hop latencies.

Also reports the executor's shape-keyed compile-cache telemetry: the
whole sweep (11 legacy arms + cascades) compiles strictly fewer pipelines
than arms.

  PYTHONPATH=src:. python benchmarks/bench_cascade.py [--quick] [--fast]

``--fast`` trains tiny 120-step families (including the mid stages) into
``results/ckpts_fast`` — the CI smoke configuration.  ``--trace-out PATH``
additionally writes a Chrome trace-event JSON of a pure-scheduling replay
over the cascade action space (no model execution): each L→M→S program
shows up as edge/mid1/device segment spans chained by hop spans, viewable
in Perfetto.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import RESULTS, TRAIN_STEPS, emit, save_json
from repro.diffusion import synth
from repro.serving import latency as lat
from repro.serving import metrics as qm
from repro.serving.arms import DEFAULT_CASCADES, build_action_space
from repro.serving.executor import Executor

RTT_MS = 80.0  # nominal edge→device link for the calibrated latency column


def _quality(xs, prompts):
    mets = [qm.quality_metrics(np.asarray(xs)[i], prompts[i])
            for i in range(len(prompts))]
    return {k: float(np.mean([m[k] for m in mets])) for k in mets[0]}


def _score(q: dict) -> float:
    """Scalar quality for the frontier: semantic alignment + preference
    proxy (the two target-similarity oracles), equally weighted."""
    return 0.5 * (q["clip"] + q["ir"])


def _frontier(points_2hop, cascade):
    """Frontier placement of one cascade point against the 2-hop sweep:
    ``dominated`` — some 2-hop arm is at least as fast AND at least as
    good; ``bracket`` — the adjacent 2-hop points by calibrated latency."""
    eps = 1e-9
    dominated = any(
        p["latency_s"] <= cascade["latency_s"] + eps
        and p["score"] >= cascade["score"] - eps
        for p in points_2hop
    )
    slower = [p for p in points_2hop if p["latency_s"] >= cascade["latency_s"]]
    faster = [p for p in points_2hop if p["latency_s"] < cascade["latency_s"]]
    lo = max(faster, key=lambda p: p["latency_s"]) if faster else None
    hi = min(slower, key=lambda p: p["latency_s"]) if slower else None
    between = (
        lo is not None and hi is not None
        and lo["score"] - eps <= cascade["score"] <= hi["score"] + eps
    )
    return {
        "dominated": dominated,
        "on_frontier": not dominated,
        "bracket": (lo["label"] if lo else None, hi["label"] if hi else None),
        "between_bracket_quality": between,
    }


def run_traced(trace_out: str, n: int = 80) -> dict:
    """Pure-scheduling cascade trace: replay a Poisson stream over the
    3-hop action space on the continuous runtime (synthetic qualities, no
    model execution) and export the relay spans as Chrome trace-event
    JSON.  Cheap — this never touches the trained families."""
    from repro.serving.arms import cascade_action_space
    from repro.serving.engine import ServingEngine, SimConfig, make_requests
    from repro.serving.obs.export import (to_chrome_trace,
                                          validate_chrome_trace,
                                          write_chrome_trace)
    from repro.serving.runtime import RuntimeConfig
    from repro.serving.workload import CyclePolicy, synthetic_quality_table

    space = cascade_action_space()
    cfg = SimConfig(n_requests=n, mean_interarrival=2.0, seed=5)
    reqs = make_requests(cfg)
    qt = synthetic_quality_table(reqs, arms=space)
    eng = ServingEngine(CyclePolicy(), qt, cfg, runtime="continuous",
                        runtime_cfg=RuntimeConfig(), arms=space)
    eng.run(reqs)
    meta = {"benchmark": "cascade", "n_arms": len(space)}
    errors = validate_chrome_trace(to_chrome_trace(eng.tracer, meta=meta))
    assert not errors, f"cascade trace schema errors: {errors[:3]}"
    write_chrome_trace(eng.tracer, trace_out, meta=meta)
    n_hops = sum(1 for s in eng.tracer.spans() if s.kind == "hop")
    emit("cascade_trace", 0.0,
         f"requests={n};coverage={eng.tracer.coverage():.3f};"
         f"hop_spans={n_hops};out={trace_out}")
    return {"coverage": eng.tracer.coverage(), "hop_spans": n_hops,
            "trace_out": trace_out}


def run(quick: bool = False, fast: bool = False, families=("XL", "F3")):
    from repro.diffusion.train import get_or_train_families

    if fast:
        fams = get_or_train_families(
            ckpt_dir=str(RESULTS / "ckpts_fast"), steps=120, verbose=True,
            with_mid=True,
        )
    else:
        fams = get_or_train_families(
            ckpt_dir=str(RESULTS / "ckpts"), steps=TRAIN_STEPS, verbose=True,
            with_mid=True,
        )
    space = build_action_space(cascades=DEFAULT_CASCADES)
    ex = Executor(fams, arms=space)
    n = 8 if quick else 24
    seeds = np.arange(6000, 6000 + n)
    prompts = [synth.sample_prompt(int(s)) for s in seeds]
    out = {}
    for fam_name in families:
        points = []
        arms = [a for a in space
                if a.program.family == fam_name or
                (a.family is None and fam_name == "XL")]
        for arm in arms:
            t0 = time.perf_counter()
            xs = ex.generate(arm, seeds)
            wall = time.perf_counter() - t0
            q = _quality(xs, prompts)
            lb = lat.program_latency(arm.program, RTT_MS)
            points.append({
                "label": arm.label,
                "n_segments": arm.program.n_segments,
                "segment_steps": [s.steps for s in arm.program.segments],
                "pools": list(arm.program.pools),
                "latency_s": lb.total,
                "segment_s": list(lb.segment_s),
                "score": _score(q),
                "wall_s": wall,
                **q,
            })
            emit(
                f"cascade_{fam_name}_{arm.label.replace('@', '_')}",
                1e6 * wall / n,
                f"latency={lb.total:.2f}s;score={_score(q):.4f};"
                f"clip={q['clip']:.4f};ir={q['ir']:.4f};"
                f"segments={arm.program.n_segments}",
            )
        two_hop = [p for p in points if p["n_segments"] == 2]
        verdicts = {}
        for p in points:
            if p["n_segments"] == 3:
                v = _frontier(two_hop, p)
                verdicts[p["label"]] = v
                emit(
                    f"cascade_frontier_{fam_name}_{p['label'].replace('@', '_')}",
                    0.0,
                    f"on_frontier={v['on_frontier']};"
                    f"bracket={v['bracket'][0]}..{v['bracket'][1]};"
                    f"between_quality={v['between_bracket_quality']}",
                )
        out[fam_name] = {"points": points, "frontier": verdicts}
    stats = ex.cache_stats()
    out["compile_cache"] = stats
    emit(
        "cascade_compile_cache", 0.0,
        f"arms={len(space)};pipelines={stats['pipelines_compiled']};"
        f"segments={stats['segment_fns_compiled']};"
        f"hit_rate={stats['cache_hit_rate']:.2f}",
    )
    n_frontier = sum(
        v["on_frontier"] for f in families for v in out[f]["frontier"].values()
    )
    n_casc = sum(len(out[f]["frontier"]) for f in families)
    emit("cascade_summary", 0.0,
         f"cascades_on_frontier={n_frontier}/{n_casc}")
    # quick/fast (CI smoke) runs must not clobber the shipped full-run numbers
    save_json("bench_cascade_quick" if (quick or fast) else "bench_cascade",
              out)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--trace-out" in sys.argv:
        run_traced(sys.argv[sys.argv.index("--trace-out") + 1])
    run(quick="--quick" in sys.argv, fast="--fast" in sys.argv)
