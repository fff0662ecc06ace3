"""``configs/sd35_relay.py``'s FLOP counts: the model's step against a hand
count and against XLA's count of the compiled segment step, and each
attention kernel call against the kernel's own cost estimate."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
MODEL = harness.load_module(BENCH / "configs" / "sd35_relay.py")
CFG = json.loads((BENCH / "configs" / "sd35-mmditx-1024.json").read_text())


def test_hand_count_tiny():
    """Width 8 in 2 heads, 2×2 patches on a 4×4×4 latent (4 image tokens),
    3 text tokens, 2 layers, the first MMDiT-X."""
    group = {"caption_projection_dim": 8, "num_layers": 2,
             "num_attention_heads": 2, "attention_head_dim": 4,
             "patch_size": 2, "qk_norm": "rms_norm", "sample_size": 4,
             "in_channels": 4}
    cfg = dict(group, text_tokens=3, cond_dim=16,
               medium=dict(group, dual_attention_layers=[0]))
    w, n_img, n_txt, patch_dim = 8, 4, 3, 16
    n = n_img + n_txt
    per_token_linear = 2 * (3 * w * w + w * w + 4 * w * w + 4 * w * w)
    attention = 2 * n * n * w * 2  # scores and values over all n queries
    ada = 2 * (2 * w * 6 * w)
    block = n * per_token_linear + attention + ada
    # last block: the text queries' attention, o_txt and MLP are dead
    last = block - n_txt * 2 * (w * w + 8 * w * w) - 2 * n_txt * n * w * 2
    embed = (2 * n_img * patch_dim * w + 2 * 16 * n_txt * w + 2 * 64 * w
             + 2 * 16 * w + 2 * n_img * w * patch_dim)
    assert MODEL.image_step_flops(cfg, "large") == block + last + embed
    # MMDiT-X: image-only q, k, v and output projections, its attention over
    # the image tokens, three more adaLN modulations
    dual = (n_img * 2 * (3 * w * w + w * w) + 2 * n_img * n_img * w * 2
            + 2 * w * 3 * w)
    assert MODEL.image_step_flops(cfg, "small") == block + last + embed + dual


def test_request_flops_splits_the_ladder():
    big = MODEL.image_step_flops(CFG, "large")
    small = MODEL.image_step_flops(CFG, "small")
    assert MODEL.request_flops(CFG, 15) == 15 * big + 35 * small


# XLA's FLOPs for one step of the compiled segment (Executor._segment_fn, a
# ladder slice one step long) for a described v5e
@pytest.mark.parametrize("role,bucket,xla", [
    ("large", 4, 2.9355e13),
    ("large", 8, 5.8708e13),
    ("small", 4, 1.1008e13),
    ("small", 8, 2.2014e13),
])
def test_matches_xla_count(role, bucket, xla):
    ours = bucket * MODEL.image_step_flops(CFG, role)
    assert abs(ours / xla - 1) < 0.005


@pytest.mark.parametrize("role", ["large", "small"])
def test_attention_counts_are_the_kernels_cost_estimate(monkeypatch, role):
    """Each kind of call of ``joint_attention_flash`` at the cell's shapes
    (traced only) hands the kernel a cost estimate of exactly the FLOPs and
    bytes that ``attention_counts`` gives."""
    from repro.kernels.flash_attention import kernel as km
    from repro.models.diffusion_nets import joint_attention_flash

    seen = []
    real = km.pl.pallas_call

    def spy(*a, **kw):
        seen.append(kw["cost_estimate"])
        return real(*a, **kw)

    monkeypatch.setattr(km.pl, "pallas_call", spy)
    jax.clear_caches()  # trace the kernel call again, past jit caches
    args = MODEL.net_args(CFG, role)
    w, heads = args["width"], args["heads"]
    n_img, n_txt = MODEL.image_tokens(CFG), CFG["text_tokens"]
    counts = MODEL.attention_counts(CFG, role, 4)
    shapes = {"joint_image": (n_img, n_img + n_txt),
              "joint_text": (n_txt, n_img + n_txt),
              "image_only": (n_img, n_img)}
    for kind, (n_q, n_k) in shapes.items():
        q = jax.ShapeDtypeStruct((4, n_q, w), jnp.float32)
        kv = jax.ShapeDtypeStruct((4, n_k, w), jnp.float32)
        jax.eval_shape(lambda q, k, v: joint_attention_flash(q, k, v, heads),
                       q, kv, kv)
        cost = seen.pop()
        assert (cost.flops, cost.bytes_accessed) == (
            counts[kind]["flops"], counts[kind]["bytes"]), kind
    assert [counts[k]["calls"] for k in shapes] == (
        [9, 8, 0] if role == "large" else [6, 5, 3])
