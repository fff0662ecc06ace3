"""A whole run at a tiny size on the CPU, with the look for a chip skipped:
sound, it comes out correct; with the timed path broken underneath, or with
the float8 control in the program's place, ``correct`` comes out false under
the committed limit."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import json
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
LIMIT = json.loads((BENCH / "configs" / "sd35-relay-1024.json").read_text()
                   )["check"]["limit"]


def _cell(traffic: str) -> harness.Cell:
    cfg = json.loads((DATA / "tiny-relay.json").read_text())
    cfg["check"] = {"number": "latent_rel_err_max", "limit": LIMIT}
    tr = json.loads((DATA / f"{traffic}.json").read_text())
    tr["check_sample"] = 1000  # compare every answer of the window
    # two readers of bench/metrics, whichever cells BENCHMARK.json holds
    return harness.Cell(f"tiny-{traffic}", 1, cfg, tr,
                        [{"name": "images_per_s", "unit": "images/s"},
                         {"name": "setup_s", "unit": "s"}], [])


def _run(traffic="tiny-steady", hook=None, seconds=0.6):
    return harness.run_cell(_cell(traffic), 2 ** 31 + 77, seconds, False,
                            t_start=time.perf_counter(), chip=False,
                            system_hook=hook)


class _Wrap:
    def __init__(self, system, serve):
        self._system, self._serve = system, serve

    def __getattr__(self, name):
        return getattr(self._system, name)

    def serve(self, arm, seeds, buckets):
        return self._serve(self._system, arm, list(seeds), buckets)


def _half_batch(system, arm, seeds, buckets):
    h = (len(seeds) + 1) // 2
    out = system.serve(arm, seeds[:h], buckets)
    return np.concatenate([out, out[: len(seeds) - h]])


def _altered(system, arm, seeds, buckets):
    out = system.serve(arm, seeds, buckets).copy()
    out[-1] = -out[-1]
    return out


def _float8_control(system, arm, seeds, buckets):
    ref = harness.load_module(BENCH / "configs" / "mmdit_relay_ref.py")
    cfg = json.loads((DATA / "tiny-relay.json").read_text())
    return ref.generate(cfg, system.params, [(arm, s) for s in seeds],
                        fp8=True)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["checks"]["latent_rel_err_max"]["value"] < LIMIT / 100
    assert res["attempted"] > 5 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}


def test_step_that_returns_its_state(monkeypatch):
    from repro.core import samplers

    monkeypatch.setattr(samplers, "rf_euler_step",
                        lambda fn, params, x, *a: x)
    assert not _run()["correct"]


def test_handoff_left_out(monkeypatch):
    """The relay's exchange: the medium segment starts from zeros instead
    of the latent the large segment handed over."""
    from repro.serving.executor import Executor

    orig = Executor._segment_fn

    def segment_fn(self, family, role, *a, **k):
        fn = orig(self, family, role, *a, **k)
        if role != "small":
            return fn
        return lambda params, x, *rest: fn(params, jnp.zeros_like(x), *rest)

    monkeypatch.setattr(Executor, "_segment_fn", segment_fn)
    assert not _run()["correct"]


@pytest.mark.parametrize("serve", [_half_batch, _altered, _float8_control],
                         ids=["half_batch_left_out", "answer_altered",
                              "float8_control"])
@pytest.mark.parametrize("traffic", ["tiny-steady", "tiny-backlog"])
def test_broken_answers_are_caught(serve, traffic):
    res = _run(traffic, hook=lambda system: _Wrap(system, serve))
    assert not res["correct"], res["checks"]
