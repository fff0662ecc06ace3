"""Parity suite for the fused int8 segment boundaries.

Three layers, three contracts:

* **Pallas kernels vs oracles (interpret mode)** — the emit/consume
  kernels (`repro.kernels.fused_sampler`) are *bit-identical* to their
  jitted jnp oracles, which in turn are locked to the
  `repro.quantization` wire halves: payload ints, scales and stepped rows
  all exact, across dtypes, ragged shapes, both sampler modes and
  guidance values.
* **Fused vs unfused execution** — `repro.core.boundary` through
  `execute_program` / `execute_graph` / the `Executor` produces the exact
  int8 payload and byte accounting, and numerically equivalent latents
  and deviations (XLA repartitions the fused program — FMA contraction
  and reciprocal-multiply selection differ per compilation unit, so
  cross-unit bitwise identity is not a property CPU XLA offers; see the
  parity contract in `repro.core.boundary`).
* **Accounting invariants** — golden runtime digests are untouched by the
  boundary layer being active, and the latency model prices a fused
  boundary at wire time alone.
"""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import boundary, samplers
from repro.core.program import RelayGraph, make_program
from repro.core.relay import execute_graph, execute_program
from repro.diffusion import synth
from repro.diffusion.families import SPECS, role_fn, role_params
from repro.kernels.fused_sampler.ops import (fused_cfg_step_dequant,
                                             fused_cfg_step_quant)
from repro.kernels.fused_sampler.ref import (fused_cfg_step_dequant_ref,
                                             fused_cfg_step_quant_ref)
from repro.quantization import (dequant_latent, latent_roundtrip,
                                latent_to_rows, payload_bytes, quant_latent,
                                quant_rowwise, relative_deviation)
from repro.serving import latency as lat
from repro.serving.arms import (Arm, dag_action_space, ensemble_program,
                                relay_program, speculative_program)
from repro.serving.executor import Executor, request_keys


def _toy_fn(params, x, t, cond):
    return 0.5 * x + 0.05 * jnp.tanh(x)


def _toy_mid_fn(params, x, t, cond):
    return 0.45 * x + 0.05 * jnp.tanh(x)


MODELS = {"large": (_toy_fn, None), "mid": (_toy_mid_fn, None),
          "small": (_toy_fn, None)}


def _toy_families():
    return {
        name: SimpleNamespace(
            spec=SPECS[name](), large_fn=_toy_fn, small_fn=_toy_fn,
            large_params=None, small_params=None,
            mid_fn=_toy_mid_fn, mid_params=None,
        )
        for name in ("XL", "F3")
    }


def _compressed_relay(family, s, quantizer="rowwise"):
    return make_program(
        SPECS[family](), [("large", "p0", s), ("small", "p1", None)],
        compress=True, quantizer=quantizer,
    )


# ---------------------------------------------------------------------------
# 1. Pallas kernels vs jnp oracles — bit parity in interpret mode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["ddim", "rf"])
@pytest.mark.parametrize("guidance", [1.0, 3.5])
@pytest.mark.parametrize("shape", [(8, 64), (3, 33), (1, 5), (13, 17)])
def test_fused_quant_kernel_bit_parity(shape, guidance, mode, dtype):
    """Emit kernel == jitted oracle to the bit: payload ints AND scales.
    Shapes include row counts that don't divide the block (the padding
    path) and single-row edge cases."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], shape, dtype)
    ec = jax.random.normal(ks[1], shape, dtype)
    eu = jax.random.normal(ks[2], shape, dtype)
    coeffs = jnp.asarray([0.4, 0.6] if mode == "ddim" else [-0.02, 0.0],
                         jnp.float32)
    q, s = fused_cfg_step_quant(x, ec, eu, coeffs, guidance=guidance,
                                mode=mode, block_r=16, interpret=True)
    qr, sr = jax.jit(
        fused_cfg_step_quant_ref, static_argnames=("guidance", "mode")
    )(x, ec, eu, coeffs.reshape(1, 2), guidance=guidance, mode=mode)
    assert q.shape == shape and s.shape == shape[:-1] + (1,)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["ddim", "rf"])
@pytest.mark.parametrize("guidance", [1.0, 3.5])
@pytest.mark.parametrize("shape", [(8, 64), (3, 33), (13, 17)])
def test_fused_dequant_kernel_bit_parity(shape, guidance, mode, dtype):
    """Consume kernel == jitted oracle to the bit, output in ε_c's dtype."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    ec = jax.random.normal(ks[1], shape, dtype)
    eu = jax.random.normal(ks[2], shape, dtype)
    qs = quant_rowwise(jax.random.normal(ks[0], shape) * 2.0)
    coeffs = jnp.asarray([0.4, 0.6] if mode == "ddim" else [-0.02, 0.0],
                         jnp.float32)
    out = fused_cfg_step_dequant(qs["q"], qs["s"], ec, eu, coeffs,
                                 guidance=guidance, mode=mode, block_r=16,
                                 interpret=True)
    ref = jax.jit(
        fused_cfg_step_dequant_ref, static_argnames=("guidance", "mode")
    )(qs["q"], qs["s"], ec, eu, coeffs.reshape(1, 2), guidance=guidance,
      mode=mode)
    assert out.dtype == ec.dtype
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(ref, np.float32)
    )


def test_quant_oracle_locked_to_wire_halves():
    """The emit oracle's quantize half IS `quant_rowwise` on the stepped
    rows — same bits as `latent_roundtrip`'s quantize on the same input —
    and the two-term update matches `samplers.step_update`."""
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 40))
    eps = jax.random.normal(jax.random.PRNGKey(4), (6, 40))
    coeffs = jnp.asarray([[0.4, 0.6]], jnp.float32)

    @jax.jit
    def oracle(x, eps):
        return fused_cfg_step_quant_ref(x, eps, eps, coeffs, guidance=1.0,
                                        mode="ddim")

    @jax.jit
    def composed(x, eps):
        out = samplers.step_update("ddim", x, eps, coeffs[0])
        qs = quant_rowwise(out)
        return qs["q"], qs["s"]

    qa, sa = oracle(x, eps)
    qb, sb = composed(x, eps)
    np.testing.assert_array_equal(np.asarray(qa), np.asarray(qb))
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


def test_kernel_backend_guardrails():
    """The kernel backends exist for the serving wire format only: emit
    with an accounting flavor or a non-rowwise quantizer must refuse
    rather than silently fall back."""
    with pytest.raises(ValueError, match="flavor='wire'"):
        boundary.emit_fn("ddim", flavor="wire_dev", use_kernel=True)
    with pytest.raises(ValueError, match="rowwise"):
        boundary.emit_fn("ddim", quantizer="log8", use_kernel=True)
    with pytest.raises(ValueError, match="rowwise"):
        boundary.consume_fn("ddim", quantizer="log8", use_kernel=True)
    with pytest.raises(ValueError, match="unknown emit flavor"):
        boundary.emit_fn("ddim", flavor="latent_only")


@pytest.mark.parametrize("kind", ["ddim", "rf"])
def test_boundary_kernel_backend_matches_jnp_backend(kind):
    """The boundary layer's two backends agree on the wire payload: the
    Pallas emit/consume (interpret) against the default jnp tails."""
    shape = (2, 8, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), shape)
    eps = jax.random.normal(jax.random.PRNGKey(6), shape)
    coeffs = jnp.asarray([0.5, 0.7] if kind == "ddim" else [-0.04, 0.0],
                         jnp.float32)
    jn = boundary.emit_fn(kind)(x, eps, eps, coeffs)["wire"]
    kn = boundary.emit_fn(kind, use_kernel=True, interpret=True)(
        x, eps, eps, coeffs)["wire"]
    np.testing.assert_array_equal(np.asarray(jn["q"]),
                                  np.asarray(kn["q"]).reshape(jn["q"].shape))
    np.testing.assert_allclose(np.asarray(jn["s"]).ravel(),
                               np.asarray(kn["s"]).ravel(), rtol=2e-7)
    out_j = boundary.consume_fn(kind)(
        jn["q"], jn["s"], eps, eps, coeffs, shape[-3:])
    out_k = boundary.consume_fn(kind, use_kernel=True, interpret=True)(
        jn["q"], jn["s"], eps, eps, coeffs, shape[-3:])
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_k),
                               rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# 2. fused step drivers vs the unfused step → roundtrip → step sequence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family,kind", [("XL", "ddim"), ("F3", "rf")])
def test_quant_dequant_step_vs_unfused(family, kind):
    """quant_step → dequant_step vs sampler-step → latent_roundtrip →
    sampler-step: exact payload bytes, matching deviation, equivalent
    latents."""
    spec = SPECS[family]()
    sig = spec.sigmas_edge
    x = jax.random.normal(jax.random.PRNGKey(7), (2,) + spec.latent_shape)
    i = 10

    res = boundary.quant_step(kind, _toy_fn, None, x, sig, i, None, None,
                              1.0, flavor="wire_dev")
    # unfused: one sampler step, then the wire roundtrip
    sample = samplers.sampler_for(kind)
    stepped, _ = sample(_toy_fn, None, x, sig, None, start=i, stop=i + 1,
                        guidance=1.0, capture_traj=False)
    rec, nbytes = latent_roundtrip(stepped, "rowwise")
    dev = float(relative_deviation(stepped, rec) * 100.0)

    assert res["bytes"] == nbytes == payload_bytes(res["wire"])
    assert float(res["dev_pct"]) == pytest.approx(dev, rel=1e-3)
    qs_u = quant_rowwise(latent_to_rows(stepped))
    np.testing.assert_array_equal(np.asarray(res["wire"]["q"]),
                                  np.asarray(qs_u["q"]))

    nxt = boundary.dequant_step(kind, _toy_fn, None, res["wire"],
                                spec.latent_shape, sig, i + 1, None, None,
                                1.0)
    rec2 = dequant_latent(res["wire"], spec.latent_shape)
    nxt_u, _ = sample(_toy_fn, None, rec2, sig, None, start=i + 1,
                      stop=i + 2, guidance=1.0, capture_traj=False)
    np.testing.assert_allclose(np.asarray(nxt), np.asarray(nxt_u),
                               rtol=2e-5, atol=2e-5)


def test_wire_dev_latent_flavor_carries_the_stepped_latent():
    spec = SPECS["XL"]()
    x = jax.random.normal(jax.random.PRNGKey(8), (2,) + spec.latent_shape)
    res = boundary.quant_step("ddim", _toy_fn, None, x, spec.sigmas_edge, 5,
                              None, None, 1.0, flavor="wire_dev_latent")
    assert set(res) == {"wire", "dev_pct", "latent", "bytes"}
    # the payload quantizes exactly that latent
    qs = quant_rowwise(latent_to_rows(res["latent"]))
    np.testing.assert_array_equal(np.asarray(res["wire"]["q"]),
                                  np.asarray(qs["q"]))


# ---------------------------------------------------------------------------
# 3. execute_program / execute_graph: fused vs unfused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantizer", ["rowwise", "log8"])
@pytest.mark.parametrize("family", ["XL", "F3"])
def test_execute_program_fused_parity(family, quantizer):
    """Linear relay with a compressed hop: exact wire bytes, no
    materialized hop latent, equivalent final latents and deviations —
    for both registered quantizers."""
    spec = SPECS[family]()
    prog = _compressed_relay(family, 20, quantizer)
    x = jax.random.normal(jax.random.PRNGKey(9), (2,) + spec.latent_shape)
    out_u, info_u = execute_program(spec, prog, MODELS, x, None,
                                    capture_traj=False)
    out_f, info_f = execute_program(spec, prog, MODELS, x, None,
                                    capture_traj=False, fused_boundary=True)
    assert info_f["transfer_bytes"] == info_u["transfer_bytes"]
    assert info_f["hops"][0]["x_out"] is None  # never materialized
    assert info_u["hops"][0]["x_out"] is not None
    assert float(info_f["handoff_deviation_pct"]) == pytest.approx(
        float(info_u["handoff_deviation_pct"]), rel=1e-3)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_u),
                               rtol=3e-5, atol=3e-5)


def test_execute_program_fused_guards():
    spec = SPECS["XL"]()
    prog = _compressed_relay("XL", 20)
    x = jax.random.normal(jax.random.PRNGKey(10), (1,) + spec.latent_shape)
    with pytest.raises(ValueError, match="capture_traj"):
        execute_program(spec, prog, MODELS, x, None, capture_traj=True,
                        fused_boundary=True)
    # a 1-step middle segment can't both consume and emit a fused boundary
    bad = make_program(
        spec, [("large", "p0", 10), ("mid", "p1", 1), ("small", "p2", None)],
        compress=True,
    )
    with pytest.raises(ValueError, match="too few steps"):
        execute_program(spec, bad, MODELS, x, None, capture_traj=False,
                        fused_boundary=True)


@pytest.mark.parametrize("graph_fn", [
    lambda: speculative_program("XL", 20, 10),
    lambda: speculative_program("F3", 20, 10),
    lambda: ensemble_program("XL", 10),
])
def test_execute_graph_fused_parity(graph_fn):
    """DAG plans: the shared fused emit feeds every same-quantizer
    consumer, byte accounting and join decisions match the unfused walk,
    latents are equivalent."""
    g = graph_fn()
    spec = SPECS[g.family]()
    x = jax.random.normal(jax.random.PRNGKey(11), (2,) + spec.latent_shape)
    out_u, info_u = execute_graph(spec, g, MODELS, x, None)
    out_f, info_f = execute_graph(spec, g, MODELS, x, None,
                                  fused_boundary=True)
    assert info_f["transfer_bytes"] == info_u["transfer_bytes"]
    assert len(info_f["hops"]) == len(info_u["hops"])
    for hu, hf in zip(info_u["hops"], info_f["hops"]):
        assert hf["transfer_bytes"] == hu["transfer_bytes"]
        assert hf["edge"] == hu["edge"]
    for ju, jf in zip(info_u["joins"], info_f["joins"]):
        assert jf.get("accepted") == ju.get("accepted")
        assert jf.get("winner") == ju.get("winner")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_u),
                               rtol=3e-5, atol=3e-5)


def test_execute_graph_fused_hops_skip_latent():
    """Every fused hop dict carries x_out=None — the boundary latent is
    not kept alive for accounting."""
    g = speculative_program("XL", 20, 10)
    spec = SPECS["XL"]()
    x = jax.random.normal(jax.random.PRNGKey(12), (1,) + spec.latent_shape)
    _, info = execute_graph(spec, g, MODELS, x, None, fused_boundary=True)
    fused_hops = [h for h in info["hops"] if h["x_out"] is None]
    assert fused_hops, "no fused hops taken on a compressed DAG"


# ---------------------------------------------------------------------------
# 4. Executor: the compiled runner vs the eager coordinators
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def executor():
    return Executor(_toy_families())


def _arm(idx, prog, label):
    return Arm(idx, prog, label)


def _eager(arm, seeds):
    """The eager unfused coordinator's images for an arm, seeded as
    ``generate_bucketed`` seeds it: one key per request from
    ``request_keys(arm.idx * 7919, seeds)``, one normal draw per key."""
    prog = arm.program
    fam = _toy_families()[prog.family]
    keys = request_keys(arm.idx * 7919, jnp.asarray(seeds, jnp.int32))
    x = jax.jit(jax.vmap(
        lambda k: jax.random.normal(k, fam.spec.latent_shape)))(keys)
    _, _, cond = synth.batch(seeds, arm.family or "XL")
    models = {r: (role_fn(fam, r), role_params(fam, r))
              for r in ("large", "mid", "small")}
    walk = execute_graph if isinstance(prog, RelayGraph) else execute_program
    out, _ = walk(fam.spec, prog, models, x, jnp.asarray(cond))
    return np.asarray(out)


def test_executor_fused_parity_linear(executor):
    seeds = np.arange(4) + 100
    arms = [
        _arm(0, _compressed_relay("XL", 20), "XL-c"),
        _arm(1, _compressed_relay("F3", 15), "F3-c"),
        _arm(2, make_program(
            SPECS["XL"](),
            [("large", "p0", 10), ("mid", "p1", 10), ("small", "p2", None)],
            compress=True), "XL-cascade-c"),
    ]
    for arm in arms:
        gf = executor.generate_bucketed(arm, seeds)
        np.testing.assert_allclose(gf, _eager(arm, seeds), rtol=3e-5,
                                   atol=3e-5, err_msg=arm.label)
        # determinism: the fused pipeline is bit-stable run-to-run
        np.testing.assert_array_equal(gf,
                                      executor.generate_bucketed(arm, seeds))


def test_executor_fused_parity_graph(executor):
    seeds = np.arange(2) + 40
    arms = [
        _arm(0, speculative_program("XL", 20, 10), "XL-spec"),
        _arm(1, ensemble_program("XL", 10), "XL-ens"),
    ]
    for arm in arms:
        gf = executor.generate_bucketed(arm, seeds)
        np.testing.assert_allclose(gf, _eager(arm, seeds), rtol=3e-5,
                                   atol=3e-5, err_msg=arm.label)


@pytest.mark.parametrize("arm", dag_action_space(), ids=lambda a: a.label)
def test_executor_matches_eager_reference(executor, arm):
    """Every arm of the DAG action space (legacy relays, speculative and
    ensemble DAGs): the compiled runner against the eager unfused walk."""
    seeds = np.arange(2) + 60
    np.testing.assert_allclose(executor.generate_bucketed(arm, seeds),
                               _eager(arm, seeds), rtol=3e-5, atol=3e-5)


def test_executor_boundary_format_keys_pipelines():
    """The fused linear pipeline needs no standalone hop fns: the wire
    payload rides inside the segment fns."""
    fams = _toy_families()
    arm = _arm(0, _compressed_relay("XL", 20), "XL-c")
    seeds = np.arange(2) + 7
    ex_f = Executor(fams)
    ex_f.generate_bucketed(arm, seeds)
    assert not ex_f._hop_fns  # the wire rides inside the segment fns
    assert set(ex_f._seg_fns) == {
        ("XL", "large", 1.0, None, "rowwise", "wire", True),
        ("XL", "small", 1.0, "rowwise", None, None, True),
    }


def test_executor_fused_validation():
    """The executor and the eager fused coordinator reject the same
    program with the same error (one fused-boundary plan decides both);
    the unfused eager walk still runs it."""
    fams = _toy_families()
    spec = SPECS["XL"]()
    bad = make_program(
        spec,
        [("large", "p0", 10), ("mid", "p1", 1), ("small", "p2", None)],
        compress=True,
    )
    ex = Executor(fams)
    with pytest.raises(ValueError, match="too few steps") as compiled:
        ex.generate_bucketed(_arm(0, bad, "bad"), np.asarray([1]))
    x = jax.random.normal(jax.random.PRNGKey(13), (1,) + spec.latent_shape)
    with pytest.raises(ValueError, match="too few steps") as eager:
        execute_program(spec, bad, MODELS, x, None, fused_boundary=True)
    assert str(compiled.value) == str(eager.value)
    execute_program(spec, bad, MODELS, x, None)


@pytest.mark.parametrize("prog_fn", [
    lambda: _compressed_relay("XL", 20),
    lambda: ensemble_program("XL", 10),
], ids=["compressed-linear", "ensemble"])
def test_select_free_plans_dispatch_without_host_reads(prog_fn,
                                                       monkeypatch):
    """A plan without a Select reads no hop deviation back: its dispatch
    runs under ``transfer_guard_device_to_host("disallow")``, its fused
    emitters return the bare payload (or the payload and the latent where
    a join needs it), and no device array is converted to a Python
    number or a numpy array.  The CPU backend's guard lets host reads of
    its own buffers through, so the conversions are trapped too
    (``jax.device_get`` goes through ``__array__``; ``np.asarray`` reads a
    CPU buffer directly, so it is trapped by name)."""
    ex = Executor(_toy_families())
    arm = _arm(0, prog_fn(), "a")
    seeds = np.arange(2) + 5
    ex.generate_bucketed(arm, seeds)  # compile outside the guard
    flavors = {k[5] for k in ex._seg_fns if k[4] is not None}
    assert flavors <= {"wire", "wire_dev_latent"} and flavors

    def host_read(*_):
        raise AssertionError("device-to-host read during dispatch")

    def no_device_arrays(to_numpy):
        def guarded(a, *args, **kw):
            if isinstance(a, jax.Array):
                host_read()
            return to_numpy(a, *args, **kw)
        return guarded

    array_t = type(jnp.zeros(()))
    for name in ("__float__", "__int__", "__bool__", "__index__", "item",
                 "tolist", "__array__"):
        monkeypatch.setattr(array_t, name, host_read)
    for name in ("asarray", "array"):  # CPU buffers skip __array__ here
        monkeypatch.setattr(np, name, no_device_arrays(getattr(np, name)))
    run = ex._runner(arm, per_key=True)
    keys = request_keys(0, jnp.asarray(seeds, jnp.int32))
    cond = jnp.asarray(synth.batch(seeds, "XL")[2])
    with jax.transfer_guard_device_to_host("disallow"):
        out = run(keys, cond)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(out),
                                  ex.generate_bucketed(arm, seeds))


# ---------------------------------------------------------------------------
# 5. warm-up + compile-cache telemetry
# ---------------------------------------------------------------------------


def test_boundary_warm_populates_cache_stats():
    boundary.clear_cache()
    n = boundary.warm((8, 8, 4), batch=2)
    stats = boundary.cache_stats()
    assert n == 8  # 2 kinds × (2 emit flavors + peek + consume)
    assert stats and all(v >= 1 for v in stats.values())
    # warming again at the same shape compiles nothing new
    boundary.warm((8, 8, 4), batch=2)
    assert boundary.cache_stats() == stats
    boundary.clear_cache()
    assert boundary.cache_stats() == {}


def test_transport_warm_boundary_opt_in():
    from repro.serving.runtime.transport import (HandoffTransport,
                                                 TransportConfig)

    boundary.clear_cache()
    HandoffTransport(TransportConfig()).warm(["XL"], boundary=False)
    assert boundary.cache_stats() == {}  # opt-in: engines don't pay this
    HandoffTransport(TransportConfig()).warm(["XL", None], boundary=True)
    stats = boundary.cache_stats()
    assert stats and all(v >= 1 for v in stats.values())
    boundary.clear_cache()


def test_executor_warm_prefires_fused_tails():
    boundary.clear_cache()
    fams = _toy_families()
    arms = [_arm(0, _compressed_relay("XL", 20), "XL-c")]
    ex = Executor(fams, arms=arms)
    stats = ex.warm()
    assert stats["pipelines_compiled"] == 1
    assert stats["boundary_traces_compiled"] >= 2  # emit + consume fired
    # the warm covered the request shape: a real request adds no compiles
    ex.generate_bucketed(arms[0], np.asarray([123]))
    after = ex.cache_stats()
    assert after["pipelines_compiled"] == stats["pipelines_compiled"]
    assert after["segment_fns_compiled"] == stats["segment_fns_compiled"]
    assert (after["boundary_traces_compiled"]
            == stats["boundary_traces_compiled"])
    boundary.clear_cache()


# ---------------------------------------------------------------------------
# 6. golden digests + latency pricing
# ---------------------------------------------------------------------------


def test_golden_digest_with_boundary_layer_active():
    """The fused boundary lives in the executor/latent layer; the serving
    engines are simulated and must not see it.  With the boundary tails
    warmed in-process, a golden regime reproduces its locked float bits."""
    from repro.serving.engine import ServingEngine, SimConfig, make_requests
    from repro.serving.workload import CyclePolicy, synthetic_quality_table

    boundary.warm((8, 8, 4), batch=2)  # active fused layer in-process
    golden = json.loads(
        (Path(__file__).parent / "golden" / "runtime_records.json")
        .read_text()
    )["clean/item"]
    cfg = SimConfig(n_requests=120, mean_interarrival=1.5, seed=11,
                    straggler_mode="item")
    reqs = make_requests(cfg)
    eng = ServingEngine(CyclePolicy(), synthetic_quality_table(reqs), cfg,
                        runtime="continuous")
    recs = sorted(eng.run(reqs), key=lambda r: r.rid)
    assert [r.arm for r in recs] == golden["arms"]
    assert [float(r.t_total).hex() for r in recs] == golden["t_total_hex"]
    assert [float(r.wait_s).hex() for r in recs] == golden["wait_hex"]


def test_latency_fused_boundary_priced_at_wire_time():
    for fam in ("XL", "F3"):
        for rtt in (0.0, 80.0):
            assert lat.handoff_seconds(fam, rtt, compressed=True,
                                       fused=True) == lat.transfer_time(
                fam, rtt, compressed=True)
            assert lat.handoff_seconds(fam, rtt, compressed=True,
                                       fused=False) == lat.transfer_time(
                fam, rtt, compressed=True) + lat.boundary_compute_seconds(
                fam, compressed=True)
    assert lat.boundary_compute_seconds(None) == 0.0
    assert lat.boundary_compute_seconds("XL", fused=True) == 0.0
    assert lat.boundary_compute_seconds("XL", compressed=False) == 0.0
    assert lat.boundary_compute_seconds("XL") > 0.0


def test_fused_boundary_under_roofline_gate():
    """The model-level version of the bench gate: a fused compressed
    boundary costs ≤ 1.1× the bare wire serialization."""
    for fam in ("XL", "F3"):
        wire = lat.wire_seconds(fam, compressed=True)
        fused = lat.handoff_seconds(fam, 0.0, compressed=True, fused=True)
        assert fused <= 1.1 * wire
        unfused = lat.handoff_seconds(fam, 0.0, compressed=True, fused=False)
        assert unfused > wire  # the roofline term is what fusion removes
