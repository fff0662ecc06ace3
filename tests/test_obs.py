"""Observability layer: span tracer invariants, Chrome trace export +
schema validation, bounded streaming statistics, latency attribution,
scheduler introspection, the event-loop profiler and the removed
``metrics`` re-export (hard ImportError with a pointer).

Cross-runtime span parity and the golden attribution test live in
tests/test_runtime_parity.py next to the rest of the parity suite.
"""
import json

import numpy as np
import pytest

from repro.serving.engine import ServingEngine, SimConfig, make_requests
from repro.serving.obs import (DepthSeries, EventLoopProfiler,
                               ReservoirSample, SchedulerIntrospection,
                               SpanTracer, StreamingQuantiles,
                               attribution_residual, latency_attribution,
                               linucb_snapshot, span_structure,
                               to_chrome_trace, validate_chrome_trace,
                               write_chrome_trace, write_spans_jsonl)
from repro.serving.runtime import RuntimeConfig
from repro.serving.workload import CyclePolicy, synthetic_quality_table


def _traced_run(runtime="continuous", n=40, profiler=None, trace=True,
                **sim_kw):
    cfg = SimConfig(n_requests=n, mean_interarrival=1.5, seed=9, **sim_kw)
    reqs = make_requests(cfg)
    qt = synthetic_quality_table(reqs)
    rt_cfg = RuntimeConfig(profiler=profiler, trace=trace)
    eng = ServingEngine(CyclePolicy(), qt, cfg, runtime=runtime,
                        runtime_cfg=rt_cfg)
    recs = eng.run(reqs)
    return eng, sorted(recs, key=lambda r: r.rid)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_tracer_manual_lifecycle():
    tr = SpanTracer()
    tr.start_request(0, 1.0, 3, "XL@10")
    tr.enqueue(0, "edge", 1.0)
    tr.start_segment(0, "edge", 2.0, "sdxl", replica=1, batch=7)
    tr.end_segment(0, 5.0)
    tr.hop(0, 0, 5.0, 5.5, 1000, compressed=True, pool="sdxl")
    tr.enqueue(0, "device", 5.5)
    tr.start_segment(0, "device", 6.0, "vega")
    tr.end_segment(0, 8.0)
    tr.end_request(0, 8.0)

    t = tr.requests[0]
    assert t.complete and t.t_total == 7.0
    assert t.attributed_s() == pytest.approx(7.0)
    assert tr.coverage() == 1.0
    assert span_structure(tr, 0) == [
        ("segment", "edge"), ("hop", "hop0"), ("segment", "device")]
    legacy = tr.legacy_view()[0]
    assert legacy["edge_start"] == 2.0 and legacy["edge_done"] == 5.0
    assert legacy["device_enqueue"] == 5.5  # post-hop queue only
    assert "edge_enqueue" not in legacy
    assert legacy["transfer_s"] == pytest.approx(0.5)
    assert legacy["transfer_bytes"] == 1000
    assert legacy["done"] == 8.0


def test_tracer_dag_branch_join_offpath():
    """DAG span kinds: branch/join markers, per-branch concurrent spans,
    sticky offpath marking, and attributed_s still tiling arrival → done."""
    tr = SpanTracer()
    tr.start_request(0, 0.0, 11, "sdxl+vega@s=20|spec=10")
    tr.enqueue(0, "edge", 0.0)
    tr.start_segment(0, "edge", 0.0, "sdxl")
    tr.end_segment(0, 4.0, name="edge")
    tr.branch_point(0, "edge", 4.0, ("spec", "ref"))
    # two branches open concurrently for the same rid
    tr.hop(0, ":edge->device~spec", 4.0, 4.5, 500, True, pool="sdxl",
           branch="spec")
    tr.enqueue(0, "edge+", 4.0, branch="ref")
    tr.start_segment(0, "edge+", 4.0, "sdxl")
    tr.enqueue(0, "device~spec", 4.5, branch="spec")
    tr.start_segment(0, "device~spec", 4.5, "vega")
    tr.end_segment(0, 7.0, name="device~spec")
    tr.hop(0, ":device~spec->select", 7.0, 7.0, 0, False, branch="spec")
    tr.end_segment(0, 8.0, name="edge+")
    # accept: the ref branch loses; resolution waits on the gate (edge+)
    tr.mark_offpath(0, "ref")
    tr.join(0, "select", 7.0, 8.0, winner="device~spec", accepted=True,
            deviation_pct=1.5, bound_pct=2.0, ignored=None)
    tr.end_request(0, 8.0)

    t = tr.requests[0]
    # the edge+ service span inherited branch="ref" from its queue span
    segs = {s.name: s for s in t.spans if s.kind == "segment"}
    assert segs["edge+"].meta["branch"] == "ref"
    assert segs["edge+"].meta.get("offpath") is True
    assert segs["device~spec"].meta["branch"] == "spec"
    assert "offpath" not in segs["device~spec"].meta
    # sticky: a late span of the resolved-away branch is flagged on append
    tr.hop(0, ":edge+->device", 8.0, 8.5, 500, True, pool="sdxl",
           branch="ref")
    assert t.spans[-1].meta["offpath"] is True
    # join meta filtered Nones and kept the outcome
    j = next(s for s in t.spans if s.kind == "join")
    assert j.meta == {"winner": "device~spec", "accepted": True,
                      "deviation_pct": 1.5, "bound_pct": 2.0}
    # attribution path (edge 4 + spec hop .5 + spec queue 0 + spec 2.5 +
    # hop 0 + join 1) tiles t_total = 8
    assert t.attributed_s() == pytest.approx(t.t_total)
    # branch/join excluded from the default structural signature
    assert all(k in ("segment", "hop") for k, _ in span_structure(tr, 0))


def test_tracer_spans_tile_lifetime_both_runtimes():
    for runtime in ("sequential", "continuous"):
        eng, recs = _traced_run(runtime, straggler_prob=0.25,
                                straggler_factor=6.0)
        assert eng.tracer.coverage() == 1.0
        assert attribution_residual(eng.tracer) < 1e-6
        for r in recs:
            assert eng.tracer.requests[r.rid].t_total == \
                pytest.approx(r.t_total, abs=1e-6)


def test_tracing_off_is_bit_identical():
    """RuntimeConfig(trace=False) must not change anything scheduler-visible
    (and leaves the tracer empty)."""
    eng_on, on = _traced_run(trace=True, straggler_prob=0.3,
                             straggler_factor=8.0)
    eng_off, off = _traced_run(trace=False, straggler_prob=0.3,
                               straggler_factor=8.0)
    assert [r.arm for r in on] == [r.arm for r in off]
    assert [r.t_total for r in on] == [r.t_total for r in off]
    assert [r.reward for r in on] == [r.reward for r in off]
    assert eng_on.fault_counters.as_dict() == eng_off.fault_counters.as_dict()
    assert len(eng_on.tracer) > 0 and len(eng_off.tracer) == 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_and_flows(tmp_path):
    eng, _ = _traced_run(straggler_prob=0.25, straggler_factor=6.0)
    trace = write_chrome_trace(eng.tracer, str(tmp_path / "t.json"),
                               meta={"k": "v"})
    assert validate_chrome_trace(trace) == []
    assert trace["otherData"] == {"k": "v"}
    on_disk = json.loads((tmp_path / "t.json").read_text())
    assert validate_chrome_trace(on_disk) == []

    evs = trace["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "s", "f"} <= phases
    assert "i" in phases  # stragglers injected → reissue instants
    # every relay request threads a flow: one s and one f per id
    for fid in {e["id"] for e in evs if e["ph"] in ("s", "t", "f")}:
        assert sum(1 for e in evs if e.get("id") == fid and e["ph"] == "s") == 1
        assert sum(1 for e in evs if e.get("id") == fid and e["ph"] == "f") == 1


def test_chrome_validator_catches_corruption():
    eng, _ = _traced_run(n=12)
    trace = to_chrome_trace(eng.tracer)
    assert validate_chrome_trace({"foo": 1})
    assert validate_chrome_trace({"traceEvents": []})
    bad = json.loads(json.dumps(trace))
    for e in bad["traceEvents"]:
        if e["ph"] == "X":
            e["dur"] = -1.0
            break
    assert any("dur" in msg for msg in validate_chrome_trace(bad))
    bad2 = json.loads(json.dumps(trace))
    bad2["traceEvents"] = bad2["traceEvents"][::-1]
    assert any("unsorted" in msg for msg in validate_chrome_trace(bad2))
    bad3 = json.loads(json.dumps(trace))
    bad3["traceEvents"] = [e for e in bad3["traceEvents"] if e["ph"] != "f"]
    assert any("finishes" in msg for msg in validate_chrome_trace(bad3))


def _traced_dag_run(runtime="continuous", n=48, **sim_kw):
    from repro.serving.arms import dag_action_space

    arms = dag_action_space()
    cfg = SimConfig(n_requests=n, mean_interarrival=1.2, seed=5, **sim_kw)
    reqs = make_requests(cfg)
    qt = synthetic_quality_table(reqs, arms=arms)
    eng = ServingEngine(CyclePolicy(), qt, cfg, runtime=runtime,
                        runtime_cfg=RuntimeConfig(trace=True), arms=arms)
    recs = eng.run(reqs)
    return eng, sorted(recs, key=lambda r: r.rid)


def test_chrome_trace_dag_branch_flows(tmp_path):
    """DAG requests export as per-branch flow tracks: a relay control
    process, branch instants, join spans carrying the select outcome, and
    every branch flow (one s, one f) anchored to its trunk flow."""
    eng, _ = _traced_dag_run()
    trace = write_chrome_trace(eng.tracer, str(tmp_path / "dag.json"))
    assert validate_chrome_trace(trace) == []
    eng_seq, _ = _traced_dag_run("sequential", n=24)
    assert validate_chrome_trace(to_chrome_trace(eng_seq.tracer)) == []
    evs = trace["traceEvents"]
    procs = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert "relay" in procs
    assert any(e["ph"] == "i" and e.get("cat") == "branch" for e in evs)
    joins = [e for e in evs if e["ph"] == "X" and e.get("cat") == "join"]
    assert joins and all("winner" in e["args"] for e in joins)
    sel = [e for e in joins if e["name"] == "join:select"]
    assert sel and all("accepted" in e["args"] for e in sel)
    # per-branch flows, each resolving, each anchored to a trunk flow
    fids = {e["id"] for e in evs if e["ph"] in ("s", "t", "f")}
    branch_fids = {f for f in fids if isinstance(f, str) and "/" in f}
    assert branch_fids  # spec/ref and a/b branch tracks exist
    assert {f.split("/", 1)[1] for f in branch_fids} >= {"spec", "ref"}
    for f in branch_fids:
        assert int(f.split("/", 1)[0]) in fids
    # losing-branch spans are drawn, tagged offpath
    assert any(e["ph"] == "X" and e["args"].get("offpath") for e in evs)


def test_chrome_validator_catches_dag_corruption():
    eng, _ = _traced_dag_run(n=20)
    trace = to_chrome_trace(eng.tracer)
    assert validate_chrome_trace(trace) == []
    bad = json.loads(json.dumps(trace))
    for e in bad["traceEvents"]:
        if e["ph"] == "i":
            del e["s"]
            break
    assert any("instant scope" in msg for msg in validate_chrome_trace(bad))
    bad2 = json.loads(json.dumps(trace))
    for e in bad2["traceEvents"]:
        if e.get("cat") == "join":
            del e["args"]["winner"]
            break
    assert any("args.winner" in msg for msg in validate_chrome_trace(bad2))
    bad3 = json.loads(json.dumps(trace))
    victim = next(e["id"] for e in bad3["traceEvents"]
                  if e["ph"] == "s" and isinstance(e["id"], str))
    trunk = victim.split("/", 1)[0]
    bad3["traceEvents"] = [
        e for e in bad3["traceEvents"]
        if not (e.get("ph") in ("s", "t", "f") and str(e["id"]) == trunk)
    ]
    assert any("no trunk flow" in msg for msg in validate_chrome_trace(bad3))


def test_spans_jsonl_roundtrip(tmp_path):
    eng, recs = _traced_run(n=12)
    path = tmp_path / "spans.jsonl"
    n_lines = write_spans_jsonl(eng.tracer, str(path))
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert len(lines) == n_lines
    reqs = [x for x in lines if x["type"] == "request"]
    assert {x["rid"] for x in reqs} == {r.rid for r in recs}
    spans = [x for x in lines if x["type"] == "span"]
    assert spans and all({"rid", "name", "kind", "t0", "t1"} <= set(s)
                         for s in spans)


# ---------------------------------------------------------------------------
# streaming stats / attribution
# ---------------------------------------------------------------------------


def test_reservoir_quantiles_bounded_and_accurate():
    rng = np.random.default_rng(0)
    xs = rng.exponential(2.0, size=50_000)
    q = StreamingQuantiles(capacity=1024, seed=1)
    for x in xs:
        q.add(x)
    s = q.summary()
    assert s["count"] == xs.size
    assert s["mean"] == pytest.approx(float(xs.mean()))
    assert s["max"] == pytest.approx(float(xs.max()))
    # reservoir quantiles approximate the empirical ones
    assert s["p50"] == pytest.approx(float(np.quantile(xs, 0.5)), rel=0.15)
    assert s["p95"] == pytest.approx(float(np.quantile(xs, 0.95)), rel=0.15)
    # bounded memory regardless of stream length
    assert q.reservoir.nbytes == 1024 * 8
    # deterministic: same seed → same reservoir
    q2 = StreamingQuantiles(capacity=1024, seed=1)
    for x in xs:
        q2.add(x)
    assert np.array_equal(q.reservoir.values(), q2.reservoir.values())


def test_reservoir_private_rng_does_not_touch_global_streams():
    rng_before = np.random.default_rng(123).integers(0, 1 << 30, 4).tolist()
    r = ReservoirSample(capacity=8, seed=0)
    for i in range(1000):
        r.add(float(i))
    assert np.random.default_rng(123).integers(
        0, 1 << 30, 4).tolist() == rng_before


def test_depth_series_exact_moments():
    d = DepthSeries(capacity=16)
    for t, depth in enumerate([0, 1, 3, 2, 7, 1]):
        d.add(float(t), depth)
    assert d.n == 6
    assert d.mean == pytest.approx(14 / 6)
    assert d.max == 7


def test_latency_attribution_shares_sum_to_one():
    eng, _ = _traced_run(straggler_prob=0.2, straggler_factor=6.0)
    att = latency_attribution(eng.tracer)
    assert "_overall" in att
    shares = sum(v["share"] for k, v in att.items() if k != "_overall")
    assert shares == pytest.approx(1.0, abs=1e-9)
    totals = sum(v["total_s"] for k, v in att.items() if k != "_overall")
    assert totals == pytest.approx(att["_overall"]["total_s"], abs=1e-6)


def test_pool_stats_depth_is_bounded():
    """Satellite bugfix lock: PoolStats queue-depth tracking is O(1) —
    no unbounded per-sample list survives a long run."""
    from repro.serving.runtime.telemetry import PoolStats, RuntimeTelemetry

    assert not hasattr(PoolStats(), "depth_samples")
    tel = RuntimeTelemetry()
    for i in range(10_000):
        tel.record_depth("vega", float(i), i % 13)
    p = tel.pools["vega"]
    assert p.depth.n == 10_000
    assert p.depth._q.reservoir.nbytes <= 1024 * 8
    s = tel.summary()["vega"]
    assert s["mean_queue_depth"] == pytest.approx(
        np.mean([i % 13 for i in range(10_000)]))
    assert s["max_queue_depth"] == 12
    assert 0 <= s["p95_queue_depth"] <= 12


# ---------------------------------------------------------------------------
# scheduler introspection
# ---------------------------------------------------------------------------


def test_scheduler_introspection_regret():
    intro = SchedulerIntrospection(3)
    for arm, r in [(0, 1.0), (1, 0.5), (0, 1.0), (2, 0.0), (1, 0.5)]:
        intro.record(arm, r)
    assert intro.best_arm == 0
    assert intro.cumulative_regret() == pytest.approx(
        (1.0 - 1.0) * 2 + (1.0 - 0.5) * 2 + (1.0 - 0.0))
    curve = intro.regret_curve()
    assert curve[-1][1] == pytest.approx(intro.cumulative_regret())
    assert all(b[1] >= a[1] - 1e-12 for a, b in zip(curve, curve[1:]))
    s = intro.summary(labels=["a", "b", "c"])
    assert s["per_arm"][0]["pulls"] == 2
    assert s["per_arm"][2]["label"] == "c"


def test_introspection_from_engine_records():
    eng, recs = _traced_run(n=30)
    intro = SchedulerIntrospection.from_records(recs, eng.n_arms)
    assert int(intro.pulls.sum()) == len(recs)
    assert intro.cumulative_regret() >= 0.0


def test_linucb_snapshot_reads_policy_state():
    from repro.core.policies import RisePolicy
    from repro.serving.context import context_dim

    d = context_dim(False)
    pol = RisePolicy(seed=0, ctx_dim=d)
    assert linucb_snapshot(object()) == {}  # non-LinUCB → empty
    rng = np.random.default_rng(0)
    for _ in range(80):
        ctx = rng.uniform(size=d)
        arm = pol.select(ctx, np.ones(len(pol.arms), bool))
        pol.update(ctx, arm, float(rng.uniform()))
    snap = linucb_snapshot(pol)
    assert snap["ctx_dim"] == d
    assert sum(snap["pulls"]) == 80
    assert len(snap["confidence_width_at_ctx"]) == snap["n_arms"]
    assert all(w > 0 for w in snap["confidence_width_at_ctx"])
    # the most-pulled arm's width shrinks below the least-pulled arm's
    widths, pulls = snap["confidence_width_at_ctx"], snap["pulls"]
    assert widths[pulls.index(max(pulls))] < widths[pulls.index(min(pulls))]


# ---------------------------------------------------------------------------
# event-loop profiler
# ---------------------------------------------------------------------------


def test_profiler_counts_and_bit_identity():
    prof = EventLoopProfiler()
    eng_p, recs_p = _traced_run(profiler=prof, straggler_prob=0.2,
                                straggler_factor=6.0)
    eng_0, recs_0 = _traced_run(profiler=None, straggler_prob=0.2,
                                straggler_factor=6.0)
    assert [r.arm for r in recs_p] == [r.arm for r in recs_0]
    assert [r.t_total for r in recs_p] == [r.t_total for r in recs_0]

    rep = prof.report()
    assert rep["events"] > 0 and rep["loop_wall_s"] > 0
    assert {"arrive", "batch_done"} <= set(rep["per_event_type"])
    assert sum(v["count"] for v in rep["per_event_type"].values()) == \
        rep["events"]
    assert sum(v["share"] for v in rep["per_event_type"].values()) == \
        pytest.approx(1.0)
    assert rep["heap_ops"]["pushes"] == rep["heap_ops"]["pops"] == \
        rep["events"]
    assert rep["heap_ops"]["peak_size"] > 0


def test_profiler_ignored_by_sequential_engine():
    prof = EventLoopProfiler()
    _traced_run("sequential", profiler=prof, n=10)
    assert prof.n_events == 0  # no event loop to profile
