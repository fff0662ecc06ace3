"""SD3.5 Large → Medium at the published block (``sd35-mmditx-1024``) at a
tiny size on the CPU: the program's ``mmdit_apply`` against the plain
reference ``configs/sd35_relay_ref.py`` step by step and over a whole relay
through ``Executor.generate_bucketed``; a whole harness run comes out
correct, and three planted faults of the block, and the float8 control in
the program's place, make it false under the committed limit. Width 128 in
2 heads of 64, 2×2 patches on an 8×8×16 latent, 2 layers (the medium role's
layer 0 MMDiT-X), 8 text tokens."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
MODEL = harness.load_module(BENCH / "configs" / "sd35_relay.py")
REF = harness.load_module(BENCH / "configs" / "sd35_relay_ref.py")
LIMIT = json.loads((BENCH / "configs" / "sd35-mmditx-1024.json").read_text()
                   )["check"]["limit"]
SEED = 2 ** 33 + 5


def _tiny() -> dict:
    return json.loads((DATA / "tiny-mmditx.json").read_text())


@pytest.fixture(scope="module")
def params():
    return MODEL.init_params(_tiny(), SEED)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_tiny_config_is_the_published_block():
    cfg = _tiny()
    for role in ("large", "small"):
        a = MODEL.net_args(cfg, role)
        assert a["width"] // a["heads"] == 64 and a["patch"] == 2
        assert a["qk_norm"] and MODEL.image_tokens(cfg) == 16
    assert MODEL.net_args(cfg, "small")["dual_layers"] == (0,)


@pytest.mark.parametrize("role", ["large", "small"])
def test_layout_is_the_programs(params, role):
    """The benchmark's parameter layout is the one ``init_mmdit`` makes."""
    from repro.models import diffusion_nets as dn

    cfg = dn.DiffNetConfig("mmdit", **MODEL.net_args(_tiny(), role))
    want = jax.eval_shape(lambda: dn.init_net(jax.random.PRNGKey(0), cfg))
    assert (jax.tree_util.tree_map(lambda a: a.shape, params[role])
            == jax.tree_util.tree_map(lambda a: a.shape, want))


@pytest.mark.parametrize("role", ["large", "small"])
@pytest.mark.parametrize("t", [0.97, 0.5, 0.02])
def test_one_step_matches_the_reference(params, role, t):
    """One x0 prediction, program against reference, in float32 on the CPU:
    the same mathematics, so only summation order differs (relative L2
    under 1e-5; the output is of the order of the input)."""
    from repro.models import diffusion_nets as dn

    cfg = _tiny()
    net = dn.DiffNetConfig("mmdit", **MODEL.net_args(cfg, role))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8, 16))
    cond = jax.random.normal(jax.random.PRNGKey(2), (3, 16))
    got = dn.mmdit_apply(params[role], x, t, cond, net)
    with jax.default_matmul_precision("highest"):
        want = REF.x0_prediction(params[role], x, t, cond,
                                 **REF._role(cfg, role))
    assert _rel(got, want) < 1e-5
    assert 0.1 < float(jnp.linalg.norm(want) / jnp.linalg.norm(x)) < 10


def test_relay_through_the_executor_matches_the_reference(params):
    """Two bucketed micro-batches of one arm through
    ``Executor.generate_bucketed`` (3 prompts padded to bucket 4, and 1)
    against the reference's one-request-at-a-time relay."""
    cfg = _tiny()
    system = MODEL.System(cfg, params)
    arm = "sd35L+M@s=10"
    seeds = [11, 12, 13, 2 ** 31 - 2]
    got = np.concatenate([system.serve(arm, seeds[:3], [1, 4]),
                          system.serve(arm, seeds[3:], [1, 4])])
    want = REF.generate(cfg, params, [(arm, s) for s in seeds])
    assert got.shape == (4, 8, 8, 16)
    assert max(_rel(g, w) for g, w in zip(got, want)) < 1e-4


def test_float8_control_is_far_from_the_reference(params):
    """The control the limit is set against is well outside it here too."""
    cfg = _tiny()
    req = [("sd35L+M@s=15", 5)]
    ref = REF.generate(cfg, params, req)
    ctl = REF.generate(cfg, params, req, fp8=True)
    assert _rel(ctl[0], ref[0]) > 2 * LIMIT


def _cell() -> harness.Cell:
    cfg = _tiny()
    cfg["check"] = {"number": "latent_rel_err_max", "limit": LIMIT}
    tr = json.loads((DATA / "tiny-backlog.json").read_text())
    tr["check_sample"] = 1000  # compare every answer of the window
    return harness.Cell("tiny-mmditx-backlog", 1, cfg, tr,
                        [{"name": "images_per_s", "unit": "images/s"},
                         {"name": "setup_s", "unit": "s"}], [])


def _run():
    return harness.run_cell(_cell(), 2 ** 31 + 99, 0.5, False,
                            t_start=time.perf_counter(), chip=False)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["checks"]["latent_rel_err_max"]["value"] < LIMIT / 100
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}


def _no_qk_norm(monkeypatch):
    from repro.models import diffusion_nets as dn

    monkeypatch.setattr(dn, "_rms_heads", lambda x, scale, heads: x)


def _no_image_only_attention(monkeypatch):
    """The served nets told of no MMDiT-X layer: the medium role's image
    stream skips its second attention (and its three modulations)."""
    orig = MODEL.System.__init__

    def init(self, cfg, params):
        orig(self, cfg, params)
        fam = self.executor.families["F3"]
        fam.large_cfg = dataclasses.replace(fam.large_cfg, dual_layers=())
        fam.small_cfg = dataclasses.replace(fam.small_cfg, dual_layers=())

    monkeypatch.setattr(MODEL.System, "__init__", init)


def _patch_order_transposed(monkeypatch):
    """Patches flattened (column, row, channel) on the way in and out:
    self-consistent, but not the published convolution's order."""
    from repro.models import diffusion_nets as dn

    def patchify(x, p):
        b, hh, ww, c = x.shape
        x = x.reshape(b, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 4, 2, 5)
        return x.reshape(b, (hh // p) * (ww // p), p * p * c)

    def unpatchify(tokens, hh, ww, p):
        b, c = tokens.shape[0], tokens.shape[-1] // (p * p)
        x = tokens.reshape(b, hh // p, ww // p, p, p, c)
        return x.transpose(0, 1, 4, 2, 3, 5).reshape(b, hh, ww, c)

    monkeypatch.setattr(dn, "patchify", patchify)
    monkeypatch.setattr(dn, "unpatchify", unpatchify)


def _float8_control(monkeypatch):
    """The reference with float8 operands in the program's place: the
    control the committed limit is set against."""
    def serve(self, arm, seeds, buckets):
        return REF.generate(_tiny(), self.params, [(arm, s) for s in seeds],
                            fp8=True)

    monkeypatch.setattr(MODEL.System, "serve", serve)


@pytest.mark.parametrize("fault", [_no_qk_norm, _no_image_only_attention,
                                   _patch_order_transposed, _float8_control],
                         ids=["qk_norm_left_out", "image_only_attention_skipped",
                              "patch_order_transposed", "float8_control"])
def test_planted_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
    assert res["checks"]["latent_rel_err_max"]["value"] > LIMIT
