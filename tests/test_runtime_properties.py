"""Property tests for the runtime's determinism contracts:

* ``Executor.generate_bucketed`` — per-sample PRNG keys make a request's
  output invariant to micro-batch composition and padding bucket (the
  aggregator may batch it with anything, pad it anywhere).  The toy
  denoiser is elementwise, so the lock is bitwise on any backend; the
  trained nets differ between buckets by up to ~7e-3 on a TPU v5e
  (``chip_smoke.py``), so a check of real nets on a TPU takes that
  tolerance;
* the shared ``repro.serving.context`` occupancy features — identical
  across both runtimes for arbitrary pool busy states (the parity suite's
  identical-arm-decisions invariant reduces to this);
* ``straggler_slow`` — request-intrinsic and deterministic, so fault
  counters are comparable across runtimes.
"""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (see requirements-dev.txt)",
)
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving import context as sctx
from repro.serving.arms import ARMS, POOL_REPLICAS
from repro.serving.engine import Pools, ServingEngine, SimConfig
from repro.serving.runtime.batching import MicroBatchAggregator
from repro.serving.runtime.engine import ContinuousRuntime, _PoolState
from repro.serving.workload import CyclePolicy


# ---------------------------------------------------------------------------
# generate_bucketed: bucket/composition invariance
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def toy_executor():
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


@settings(max_examples=10, deadline=None)
@given(
    seeds=st.lists(st.integers(0, 500), min_size=1, max_size=8, unique=True),
    companions=st.lists(st.integers(501, 999), min_size=0, max_size=7,
                        unique=True),
    arm_idx=st.sampled_from([0, 2, 8]),  # standalone, XL relay, F3 relay
)
def test_generate_bucketed_composition_invariant(toy_executor, seeds,
                                                 companions, arm_idx):
    """Each sample's generation depends only on its own seed: identical
    whether generated alone, inside any micro-batch, or padded to any
    bucket."""
    arm = ARMS[arm_idx]
    batch = np.array(seeds)
    out = toy_executor.generate_bucketed(arm, batch)
    assert out.shape[0] == len(seeds)
    # alone (bucket 1 or the smallest fitting bucket)
    solo = toy_executor.generate_bucketed(arm, batch[:1])
    np.testing.assert_allclose(solo[0], out[0], rtol=1e-5, atol=1e-6)
    # embedded in a different (larger/differently-padded) micro-batch
    mixed = np.concatenate([np.array(companions[: 8 - len(seeds)]), batch[:1]])
    out_mixed = toy_executor.generate_bucketed(arm, mixed)
    np.testing.assert_allclose(out_mixed[-1], out[0], rtol=1e-5, atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(
    seeds=st.lists(st.integers(0, 500), min_size=2, max_size=8, unique=True),
    arm_idx=st.sampled_from([0, 2, 8]),
    data=st.data(),
)
def test_generate_bucketed_subset_matches_full(toy_executor, seeds, arm_idx,
                                               data):
    """Partial-batch re-execution property: for ANY index subset of ANY
    micro-batch (any order, any size, hence any re-issue bucket), the
    subset re-run is bit-identical to the corresponding rows of the full
    ``generate_bucketed`` call — the contract that makes per-item straggler
    re-issue on a twin replica output-transparent."""
    arm = ARMS[arm_idx]
    batch = np.array(seeds)
    full = toy_executor.generate_bucketed(arm, batch)
    subset = data.draw(
        st.lists(st.integers(0, len(seeds) - 1), min_size=1,
                 max_size=len(seeds), unique=True),
        label="subset",
    )
    part = toy_executor.generate_bucketed(arm, batch, subset=subset)
    np.testing.assert_array_equal(part, full[np.asarray(subset)])


# ---------------------------------------------------------------------------
# shared occupancy features: identical across runtimes
# ---------------------------------------------------------------------------


def _continuous_pools(cfg, busy, horizon=10.0):
    rt = ContinuousRuntime(CyclePolicy(), None, cfg)
    rt.pools = {
        p: _PoolState(
            n=n, free=[i for i in range(n) if not busy[p][i]],
            busy_until=[horizon if busy[p][i] else 0.0 for i in range(n)],
            agg=MicroBatchAggregator(p),
        )
        for p, n in POOL_REPLICAS.items()
    }
    return rt


N_REPLICAS = sum(POOL_REPLICAS.values())


@settings(max_examples=40, deadline=None)
@given(busy_bits=st.lists(st.booleans(), min_size=N_REPLICAS,
                          max_size=N_REPLICAS))
def test_occupancy_features_identical_across_runtimes(busy_bits):
    """For any pool busy pattern, the sequential engine and the continuous
    runtime compute the same context load features — both delegate to
    serving.context.aggregate_occupancy."""
    cfg = SimConfig()
    bits = iter(busy_bits)
    busy = {p: [next(bits) for _ in range(n)] for p, n in POOL_REPLICAS.items()}
    now = 5.0

    pools = Pools(cfg)
    for p, flags in busy.items():
        pools.free_at[p] = [10.0 if f else 0.0 for f in flags]
    eng = ServingEngine(CyclePolicy(), None, cfg, runtime="sequential")
    occ_seq = eng._occupancies(pools, now)

    rt = _continuous_pools(cfg, busy)
    occ_cont = rt._occupancies(now)

    expected = sctx.aggregate_occupancy(
        {p: float(np.mean(flags)) for p, flags in busy.items()}
    )
    assert occ_seq == pytest.approx(expected)
    assert occ_cont == pytest.approx(expected)
    assert set(occ_seq) == set(occ_cont) == {"vega", "sdxl", "sd3"}


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**20),
    rid=st.integers(0, 10_000),
    prob=st.floats(0.0, 1.0),
    factor=st.floats(1.0, 50.0),
)
def test_straggler_slow_is_request_intrinsic(seed, rid, prob, factor):
    cfg = SimConfig(seed=seed, straggler_prob=prob, straggler_factor=factor)
    a = sctx.straggler_slow(cfg, rid)
    assert a == sctx.straggler_slow(cfg, rid)  # deterministic
    assert a in (1.0, float(factor))
    if prob == 0.0:
        assert a == 1.0
