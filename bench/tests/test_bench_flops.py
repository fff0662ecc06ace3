"""The benchmark's model-FLOP count against hand counts and against XLA's
count of the compiled segment step (v5e, one step, from
``bench/compile_segments.py``; PERF.md lists them)."""
import bench_paths  # noqa: F401  (the benchmark and src on the path)
import json
from pathlib import Path

import pytest

import harness

BENCH = Path(__file__).resolve().parents[1]
MODEL = harness.load_module(BENCH / "configs" / "mmdit_relay.py")


def _cfg(name):
    """A configuration file, or "sd35-relay-512": the 1024 file at 512x512
    (1024 image tokens)."""
    if name == "sd35-relay-512":
        return dict(_cfg("sd35-relay-1024"), latent_hw=32,
                    buckets=[1, 2, 4, 8])
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_hand_count_tiny():
    cfg = {"caption_projection_dim": 8, "num_layers": 2, "in_channels": 4,
           "latent_hw": 2, "text_tokens": 3, "cond_dim": 16,
           "medium": {"caption_projection_dim": 4, "num_layers": 1}}
    w, n_img, n_txt = 8, 4, 3
    n = n_img + n_txt
    per_token_linear = 2 * (3 * w * w + w * w + 4 * w * w + 4 * w * w)
    attention = 2 * n * n * w * 2  # scores and values over all n queries
    ada = 2 * (2 * w * 6 * w)
    block = n * per_token_linear + attention + ada
    # last block: the text queries' attention, o_txt and MLP are dead
    last = block - n_txt * 2 * (w * w + 8 * w * w) - 2 * n_txt * n * w * 2
    embed = (2 * n_img * 4 * w + 2 * 16 * n_txt * w + 2 * 64 * w
             + 2 * 16 * w + 2 * n_img * w * 4)
    assert MODEL.image_step_flops(cfg, "large") == block + last + embed


def test_request_flops_splits_the_ladder():
    cfg = _cfg("sd35-relay-512")
    big = MODEL.image_step_flops(cfg, "large")
    small = MODEL.image_step_flops(cfg, "small")
    assert MODEL.request_flops(cfg, 15) == 15 * big + 35 * small
    assert 3.5 < big / small < 4.0


# XLA's FLOPs for one step of the compiled segment, for a described v5e
# (bench/compile_segments.py)
@pytest.mark.parametrize("name,role,bucket,xla", [
    ("sd35-relay-512", "large", 1, 1.86e12),
    ("sd35-relay-512", "large", 8, 1.49e13),
    ("sd35-relay-512", "small", 1, 5.14e11),
    ("sd35-relay-512", "small", 8, 4.11e12),
    ("sd35-relay-1024", "large", 4, 2.94e13),
    ("sd35-relay-1024", "large", 8, 5.87e13),
    ("sd35-relay-1024", "small", 4, 8.84e12),
])
def test_matches_xla_count(name, role, bucket, xla):
    ours = bucket * MODEL.image_step_flops(_cfg(name), role)
    assert abs(ours / xla - 1) < 0.01
