"""Continuous-batching relay runtime tests: aggregator bucketing, two-phase
handoff ordering, compressed-transport quality bounds, throughput vs the
sequential engine, telemetry export."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import RoundRobinPolicy
from repro.core.relay import (FamilySpec, latent_norms, make_relay_plan,
                              per_step_deviation, relay_generate)
from repro.core.schedules import karras_sigmas
from repro.serving import latency as lat
from repro.serving.arms import ARMS, N_ARMS
from repro.serving.engine import (ServingEngine, SimConfig, make_requests,
                                  summarize)
from repro.serving.obs.export import export_runtime_telemetry
from repro.serving.runtime import (EDGE, HandoffTransport, MicroBatchAggregator,
                                   RuntimeConfig, TransportConfig, WorkItem,
                                   batch_key_for, bucketize)
from repro.serving.runtime.events import DEVICE
from repro.serving.workload import CyclePolicy, synthetic_quality_table


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _item(rid, arm_idx, phase="edge", steps=5):
    from repro.core.context import Request

    req = Request(rid=rid, arrival=0.0, complexity=0.5, wants_text=False,
                  rtt_ms=80.0, battery=0.9, pref_speed=0.5, prompt_seed=rid)
    arm = ARMS[arm_idx]
    pool = arm.edge_pool if phase == "edge" else arm.device_pool
    return WorkItem(req, arm_idx, phase, pool, steps)


def run_engine(policy, n, mu, runtime, rt_cfg=None, seed=3):
    cfg = SimConfig(n_requests=n, mean_interarrival=mu, seed=seed)
    reqs = make_requests(cfg)
    qt = synthetic_quality_table(reqs)
    eng = ServingEngine(policy, qt, cfg, runtime=runtime, runtime_cfg=rt_cfg)
    recs = eng.run(reqs)
    return eng, reqs, recs


# ---------------------------------------------------------------------------
# aggregator bucketing
# ---------------------------------------------------------------------------

def test_bucketize():
    assert [bucketize(n) for n in (1, 2, 3, 4, 5, 8)] == [1, 2, 4, 4, 8, 8]
    with pytest.raises(ValueError):
        bucketize(9)


def test_aggregator_coalesces_only_matching_keys():
    agg = MicroBatchAggregator("sd3l", linger_s=0.25)
    for rid in range(3):
        agg.push(_item(rid, 6), now=0.0)  # s=5 relay arm
    for rid in range(3, 5):
        agg.push(_item(rid, 7), now=0.0)  # s=10 relay arm: different program
    assert agg.depth() == 5
    items, bucket = agg.next_batch(now=10.0)  # past linger
    assert [it.rid for it in items] == [0, 1, 2]
    assert bucket == 4  # 3 items pad to the 4-bucket
    assert len({batch_key_for(it) for it in items}) == 1
    items2, bucket2 = agg.next_batch(now=10.0)
    assert [it.rid for it in items2] == [3, 4] and bucket2 == 2
    assert agg.depth() == 0


def test_aggregator_lingers_then_flushes():
    agg = MicroBatchAggregator("sd3l", linger_s=0.25)
    agg.push(_item(0, 6), now=1.0)
    assert agg.next_batch(now=1.05) is None  # young sub-maximal batch waits
    assert agg.flush_deadline() == pytest.approx(1.25)
    assert agg.next_batch(now=1.05, force=True) is not None  # forced flush
    agg.push(_item(1, 6), now=2.0)
    assert agg.next_batch(now=2.3) is not None  # linger expired: dispatch


def test_aggregator_full_batch_bypasses_lingering_older_key():
    """A full bucket of a newer key dispatches immediately instead of
    waiting head-of-line behind an older sub-maximal lingering key."""
    agg = MicroBatchAggregator("sd3l", linger_s=0.25)
    agg.push(_item(0, 6), now=0.0)  # older key, 1 item, still lingering
    for rid in range(1, 9):
        agg.push(_item(rid, 7), now=0.01)  # newer key fills the 8-bucket
    items, bucket = agg.next_batch(now=0.02)
    assert [it.rid for it in items] == list(range(1, 9)) and bucket == 8
    assert agg.next_batch(now=0.02) is None  # old key still lingers
    assert agg.next_batch(now=0.02, force=True) is not None


def test_aggregator_caps_batch_at_largest_bucket():
    agg = MicroBatchAggregator("sd3l")
    for rid in range(11):
        agg.push(_item(rid, 6), now=0.0)
    items, bucket = agg.next_batch(now=5.0)
    assert len(items) == 8 and bucket == 8
    assert agg.depth() == 3


# ---------------------------------------------------------------------------
# two-phase handoff ordering
# ---------------------------------------------------------------------------

def test_two_phase_ordering():
    eng, reqs, recs = run_engine(RoundRobinPolicy(), n=80, mu=2.0,
                                 runtime="continuous")
    assert len(recs) == 80
    saw_relay = 0
    for rid, tr in eng.trace.items():
        assert tr["done"] >= tr["arrival"]
        if "edge_start" in tr:  # relay arm: edge → transfer → device
            saw_relay += 1
            assert tr["arrival"] <= tr["edge_start"] <= tr["edge_done"]
            assert tr["device_enqueue"] == pytest.approx(
                tr["edge_done"] + tr["transfer_s"]
            )
            assert tr["device_start"] >= tr["device_enqueue"] - 1e-9
            assert tr["done"] >= tr["device_start"]
            assert tr["transfer_bytes"] > 0
        else:  # standalone: single device phase
            assert tr["device_start"] >= tr["arrival"]
    assert saw_relay > 20


def test_records_compatible_with_summarize():
    _, _, recs = run_engine(RoundRobinPolicy(), n=60, mu=2.0,
                            runtime="continuous")
    s = summarize(recs)
    assert np.isfinite(s["total_reward"])
    assert 0.0 <= s["text_fraction"] <= 1.0
    assert len(s["arm_histogram"]) == N_ARMS


def test_unknown_runtime_rejected():
    with pytest.raises(ValueError):
        ServingEngine(RoundRobinPolicy(), None, SimConfig(), runtime="warp")


# ---------------------------------------------------------------------------
# throughput: continuous batching vs sequential at high arrival rate
# ---------------------------------------------------------------------------

def test_continuous_runtime_doubles_throughput():
    def throughput(runtime):
        _, reqs, recs = run_engine(CyclePolicy(), n=300, mu=0.25,
                                   runtime=runtime)
        done = max(r.t_total + reqs[r.rid].arrival for r in recs)
        arms = [r.arm for r in sorted(recs, key=lambda r: r.rid)]
        return len(recs) / (done - reqs[0].arrival), arms

    th_seq, arms_seq = throughput("sequential")
    th_cont, arms_cont = throughput("continuous")
    assert arms_seq == arms_cont  # identical per-request arm decisions
    assert th_cont >= 2.0 * th_seq, (th_seq, th_cont)


def test_policy_sees_per_request_context():
    """The runtime still makes one policy decision per request, with a
    full-dimension context (batching is an execution detail)."""

    class Spy(CyclePolicy):
        def __init__(self):
            super().__init__()
            self.ctxs = []

        def select(self, ctx, avail):
            self.ctxs.append(np.array(ctx))
            assert avail.shape == (N_ARMS,)
            return super().select(ctx, avail)

    spy = Spy()
    run_engine(spy, n=50, mu=1.0, runtime="continuous")
    assert len(spy.ctxs) == 50
    assert all(c.shape == (8,) for c in spy.ctxs)


# ---------------------------------------------------------------------------
# compressed latent handoff transport
# ---------------------------------------------------------------------------

def test_latent_wire_bytes_compression_ratio():
    for fam in ("XL", "F3"):
        raw = lat.latent_wire_bytes(fam)
        comp = lat.latent_wire_bytes(fam, compressed=True)
        assert raw == lat.LATENT_BYTES[fam]
        assert comp < raw / 1.9  # int8 + per-channel scales ≈ half of fp16
    assert lat.latent_wire_bytes(None) == 0
    assert lat.transfer_time("XL", 80.0, compressed=True) < lat.transfer_time(
        "XL", 80.0, compressed=False
    )


def test_transport_quality_delta_bounds():
    tr = HandoffTransport(TransportConfig(compress=True))
    err = tr.handoff_error("XL")
    assert 0.0 < err < 0.02  # row-wise int8 keeps relative error < 2 %
    q = {"clip": 0.8, "ir": 0.7, "aes": 5.5, "pick": 0.22, "ocr": 0.0}
    dq = tr.quality_delta("XL", q)
    assert dq["clip"] < q["clip"] and dq["ir"] < q["ir"]
    assert dq["clip"] > 0.97 * q["clip"]  # ...but only marginally
    assert dq["aes"] == q["aes"]  # target-free metrics untouched
    # subtractive penalty: negative scores also degrade (never improve)
    neg = tr.quality_delta("XL", {"clip": -0.5, "ir": -1.0})
    assert neg["clip"] < -0.5 and neg["ir"] < -1.0
    off = HandoffTransport(TransportConfig(compress=False))
    assert off.quality_delta("XL", q) == q


def _toy_relay(compress):
    spec = FamilySpec(
        name="XL", kind="ddim",
        sigmas_edge=karras_sigmas(12), sigmas_device=karras_sigmas(8),
        latent_shape=(8, 8, 4),
    )
    plan = make_relay_plan(spec, 6)

    def eps_fn(params, x, sig, cond):
        return 0.5 * x  # deterministic toy denoiser

    x0 = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))
    cond = jnp.zeros((2, 4))
    return relay_generate(
        spec, plan, eps_fn, None, eps_fn, None, x0, cond, cond,
        compress_handoff=compress,
    )


def test_compressed_handoff_deviation_bound():
    """Int8 round-trip of the relay latent keeps the Eq. 1 per-step
    deviation of the device trajectory under 2 %."""
    x_u, info_u = _toy_relay(compress=False)
    x_c, info_c = _toy_relay(compress=True)
    assert float(info_u["handoff_deviation_pct"]) == 0.0
    assert 0.0 < float(info_c["handoff_deviation_pct"]) < 2.0
    dev = per_step_deviation(
        np.asarray(latent_norms(info_u["traj_device"])),
        np.asarray(latent_norms(info_c["traj_device"])),
    )
    assert dev.max() < 2.0, dev


def test_compressed_handoff_transfer_bytes():
    _, info_u = _toy_relay(compress=False)
    _, info_c = _toy_relay(compress=True)
    elems = 2 * 8 * 8 * 4
    assert info_u["transfer_bytes"] == elems * 4  # raw fp32 latent
    # int8 payload + one fp32 scale per (sample, channel) row
    assert info_c["transfer_bytes"] == elems + 2 * 4 * 4
    assert info_c["transfer_bytes"] < info_u["transfer_bytes"] // 3


def test_compressed_handoff_batch_independent():
    """Quantization rows never cross the batch dim: a sample's round-trip
    is unchanged by a large-amplitude batch companion."""
    from repro.quantization import latent_roundtrip_int8

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 4))
    loud = x.at[1].multiply(100.0)
    rec_a, _ = latent_roundtrip_int8(x)
    rec_b, _ = latent_roundtrip_int8(loud)
    np.testing.assert_allclose(rec_a[0], rec_b[0], rtol=0, atol=0)


def _toy_executor():
    """Executor over toy denoisers: exercises the real jit/bucketing/seeding
    machinery without trained families.  The toy denoiser is elementwise,
    so bucket and subset invariance hold bitwise on any backend (trained
    nets differ between buckets by up to ~7e-3 on a TPU v5e)."""
    from types import SimpleNamespace

    from repro.diffusion.families import SPECS
    from repro.serving.executor import Executor

    def toy_fn(params, x, t, cond):
        return 0.5 * x

    fams = {
        name: SimpleNamespace(
            spec=SPECS[name](), large_fn=toy_fn, small_fn=toy_fn,
            large_params=None, small_params=None,
        )
        for name in ("XL", "F3")
    }
    return Executor(fams)


def test_generate_bucketed_invariant_to_bucket():
    """Per-sample PRNG keys: a request's generation is identical whichever
    pad-to-bucket micro-batch shape it lands in."""
    ex = _toy_executor()
    for arm in (ARMS[0], ARMS[2]):  # standalone + an XL relay arm
        seeds = np.arange(5) + 100
        out5 = ex.generate_bucketed(arm, seeds)  # bucket 8
        out1 = ex.generate_bucketed(arm, seeds[:1])  # bucket 1
        assert out5.shape[0] == 5 and out1.shape[0] == 1
        np.testing.assert_allclose(out1[0], out5[0], rtol=1e-5, atol=1e-6)


def test_generate_bucketed_subset_bit_identical():
    """Partial-batch re-execution (the straggler re-issue path): re-running
    any index subset of a micro-batch — padded to its own, smaller bucket —
    reproduces the corresponding rows of the full call bit-for-bit, so a
    twin replica can re-run just the stragglers without perturbing their
    outputs."""
    ex = _toy_executor()
    seeds = np.arange(7) + 400
    for arm in (ARMS[0], ARMS[2], ARMS[8]):  # standalone, XL relay, F3 relay
        full = ex.generate_bucketed(arm, seeds)  # pads to the 8-bucket
        for subset in ([2], [1, 4, 6], [6, 0, 3], list(range(7))):
            part = ex.generate_bucketed(arm, seeds, subset=subset)
            assert part.shape[0] == len(subset)
            np.testing.assert_array_equal(part, full[np.asarray(subset)])


def test_generate_bucketed_empty_subset_rejected():
    ex = _toy_executor()
    with pytest.raises(ValueError, match="empty subset"):
        ex.generate_bucketed(ARMS[0], np.arange(4), subset=[])


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_telemetry_export():
    eng, _, recs = run_engine(CyclePolicy(), n=120, mu=0.5,
                              runtime="continuous")
    tel = export_runtime_telemetry(eng.telemetry)
    assert set(tel) == {"sd3l", "sd3m", "sdxl", "vega"}
    for pool, t in tel.items():
        assert 0.0 < t["batch_occupancy"] <= 1.0
        assert t["n_batches"] > 0
        assert t["mean_queue_depth"] >= 0.0
    # only edge pools ship latents over the wire
    assert tel["sdxl"]["bytes_transferred"] > 0
    assert tel["sd3l"]["bytes_transferred"] > 0
    assert tel["vega"]["bytes_transferred"] == 0
    # compression halves bytes-on-wire vs the raw runtime
    eng_raw, _, _ = run_engine(CyclePolicy(), n=120, mu=0.5,
                               runtime="continuous",
                               rt_cfg=RuntimeConfig(compress_handoff=False))
    raw = export_runtime_telemetry(eng_raw.telemetry)
    assert tel["sd3l"]["bytes_transferred"] < raw["sd3l"]["bytes_transferred"] / 1.9
    assert export_runtime_telemetry(None) == {}


def test_backpressure_steers_availability():
    """Under heavy load the backlog horizon masks saturated pools, so an
    avail-respecting policy sees genuine backpressure."""

    class AvailSpy(CyclePolicy):
        def __init__(self):
            super().__init__()
            self.masked = 0

        def select(self, ctx, avail):
            self.masked += int(not avail.all())
            return super().select(ctx, avail)

    spy = AvailSpy()
    run_engine(spy, n=250, mu=0.2, runtime="continuous")
    assert spy.masked > 0
