"""SD3.5 Large → Medium at their published block, as the system under test
serves them.

The configuration file holds each role's published keys (the large role at
its top level, the medium role in ``medium``): 64-wide heads, qk-RMSNorm,
2×2 patches on the 128×128×16 latent and, in Medium, MMDiT-X layers whose
image stream adds a self-attention of its own. This module builds both roles
from a seed in the parameter layout of
``repro.models.diffusion_nets.mmdit_apply``, serves them through the F3
``Family`` and ``Executor.generate_bucketed`` (the timed path), and counts
the model's FLOPs and each attention kernel call's FLOPs and bytes from
shapes.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from harness import load_module

_base = load_module(Path(__file__).with_name("mmdit_relay.py"))
ROLES = _base.ROLES


def net_args(cfg: dict, role: str) -> dict:
    """``DiffNetConfig`` keywords of one role, from its published keys: the
    file's top level for the large role, its ``medium`` group for the small
    one."""
    r = cfg if role == "large" else cfg["medium"]
    width, heads = r["caption_projection_dim"], r["num_attention_heads"]
    if heads * r["attention_head_dim"] != width:
        raise ValueError(f"{role}: {heads} heads of {r['attention_head_dim']}"
                         f" do not make width {width}")
    return dict(width=width, depth=r["num_layers"], heads=heads,
                latent_hw=r["sample_size"], latent_ch=r["in_channels"],
                cond_dim=cfg["cond_dim"], text_tokens=cfg["text_tokens"],
                patch=r["patch_size"], qk_norm=r["qk_norm"] == "rms_norm",
                dual_layers=tuple(r.get("dual_attention_layers", ())))


def latent_shape(cfg: dict) -> tuple:
    return (cfg["sample_size"], cfg["sample_size"], cfg["in_channels"])


def image_tokens(cfg: dict) -> int:
    return (cfg["sample_size"] // cfg["patch_size"]) ** 2


def _role_shapes(cfg: dict, role: str) -> dict:
    a = net_args(cfg, role)
    w, dh = a["width"], a["width"] // a["heads"]
    patch_dim = a["patch"] ** 2 * a["latent_ch"]

    def layer(i):
        dual = i in a["dual_layers"]
        lp = {
            "ada_img": (w, (9 if dual else 6) * w), "ada_txt": (w, 6 * w),
            "qkv_img": (w, 3 * w), "qkv_txt": (w, 3 * w),
            "o_img": (w, w), "o_txt": (w, w),
            "mlp1_img": (w, 4 * w), "mlp2_img": (4 * w, w),
            "mlp1_txt": (w, 4 * w), "mlp2_txt": (4 * w, w),
        }
        streams = ("img", "txt")
        if dual:
            lp.update(qkv_x=(w, 3 * w), o_x=(w, w))
            streams += ("x",)
        if a["qk_norm"]:
            for s in streams:
                lp[f"q_norm_{s}"] = (dh,)
                lp[f"k_norm_{s}"] = (dh,)
        return lp

    return {
        "patch": (patch_dim, w),
        "pos": (image_tokens(cfg), w),
        "txt_proj": (cfg["cond_dim"], cfg["text_tokens"] * w),
        "t_emb": (64, w),
        "c_emb": (cfg["cond_dim"], w),
        "layers": [layer(i) for i in range(a["depth"])],
        "out_norm": (w,),
        "out": (w, patch_dim),
    }


def param_shapes(cfg: dict) -> dict:
    """{role: tree of shape tuples} in the program's parameter layout."""
    return {role: _role_shapes(cfg, role) for role in ROLES}


def _leaf(key, name: str, shape):
    """One seeded weight. Gates and modulations are random (not adaLN-Zero),
    so every block moves the output; the qk-RMSNorm scales are near 1.5, so
    that q.k/8 spreads with a standard deviation near 2.3 and attention is
    not a plain mean over 4096-4429 keys."""
    x = jax.random.normal(key, shape, jnp.float32)
    if name.startswith(("q_norm_", "k_norm_")):
        return 1.5 + 0.25 * x
    if name in ("ada_img", "ada_txt"):
        return x * (0.5 / np.sqrt(shape[0]))
    if name in ("pos", "out_norm"):
        return x * 0.1
    return x / np.sqrt(shape[0])


def init_params(cfg: dict, seed: int) -> dict:
    """Every weight of both roles from ``seed``, on the device, in one
    jitted call, in float32 as the program holds them."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = [str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))
             for path, _ in flat]
    leaf_shapes = [shape for _, shape in flat]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaf_shapes))
        leaves = [_leaf(keys[i], names[i], leaf_shapes[i])
                  for i in range(len(leaf_shapes))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return make(_base._seed_key(seed))


def image_step_flops(cfg: dict, role: str) -> float:
    """Model FLOPs of one denoising step of one image: per block the
    linear layers of both streams (24 N w^2), the joint attention
    (4 N^2 w) and the two adaLN projections; per MMDiT-X block the image
    stream's second attention over the image tokens (4 N_img^2 w), its
    q, k, v and output projections (8 N_img w^2) and three more adaLN
    modulations; plus the patch embedding, the text and conditioning
    embeddings and the output projection. N counts image and text tokens.
    In the last block only the text stream's keys and values reach the
    output, so its text queries' attention, output projection and MLP are
    not counted; XLA removes them too. Norms and other elementwise work
    are not counted."""
    a = net_args(cfg, role)
    w, depth = a["width"], a["depth"]
    n_img, n_txt = image_tokens(cfg), cfg["text_tokens"]
    n = n_img + n_txt
    patch_dim, cond = a["patch"] ** 2 * a["latent_ch"], cfg["cond_dim"]
    block = 24 * n * w * w + 4 * n * n * w + 2 * 2 * w * 6 * w
    dual = 8 * n_img * w * w + 4 * n_img * n_img * w + 2 * w * 3 * w
    last_txt_unused = n_txt * 2 * 9 * w * w + 4 * n_txt * n * w
    embed = 2 * n_img * patch_dim * w + 2 * cond * n_txt * w + 2 * 64 * w \
        + 2 * cond * w + 2 * n_img * w * patch_dim
    return float(depth * block + len(a["dual_layers"]) * dual
                 - last_txt_unused + embed)


def request_flops(cfg: dict, relay_step: int) -> float:
    """Model FLOPs of one relay request: s large steps, then the medium
    model for the rest of the ladder."""
    steps = cfg["steps"]
    return (relay_step * image_step_flops(cfg, "large")
            + (steps - relay_step) * image_step_flops(cfg, "small"))


def attention_counts(cfg: dict, role: str, bucket: int) -> dict:
    """Each kind of attention kernel call in one step of ``role`` at
    ``bucket`` images: {kind: {"calls", "flops", "bytes"}}, FLOPs and HBM
    bytes per call from the kernel's own cost estimate, on the blocks the
    program picks. Kinds: ``joint_image`` and ``joint_text`` (the image and
    text queries over the joint keys, in scope ``mmdit/attention``; the last
    block's text queries are dead code) and ``image_only`` (MMDiT-X, in
    scope ``mmdit/attention_x``)."""
    from repro.kernels.flash_attention.ops import packed_cost
    from repro.models.diffusion_nets import attention_blocks

    a = net_args(cfg, role)
    w, heads = a["width"], a["heads"]
    n_img, n_txt = image_tokens(cfg), cfg["text_tokens"]

    def call(n_q, n_k):
        block_q, block_k = attention_blocks(n_q, n_k, heads, w // heads)
        c = packed_cost(bucket, n_q, n_k, w, w, heads, block_q=block_q,
                        block_k=block_k)
        return {"flops": c.flops, "bytes": c.bytes_accessed}

    return {
        "joint_image": dict(call(n_img, n_img + n_txt), calls=a["depth"]),
        "joint_text": dict(call(n_txt, n_img + n_txt), calls=a["depth"] - 1),
        "image_only": dict(call(n_img, n_img), calls=len(a["dual_layers"])),
    }


class System(_base.System):
    """The program's relay serving path at this configuration: the F3
    family built from the benchmark's weights, served by an Executor."""

    def __init__(self, cfg: dict, params: dict):
        from repro.core.relay import FamilySpec
        from repro.core.schedules import rf_times
        from repro.diffusion.families import Family
        from repro.models.diffusion_nets import DiffNetConfig
        from repro.serving.arms import ARMS
        from repro.serving.executor import Executor

        self.params = params
        ladder = rf_times(cfg["steps"])
        spec = FamilySpec(name="F3", kind="rf", sigmas_edge=ladder,
                          sigmas_device=ladder, sigmas_mid=ladder,
                          latent_shape=latent_shape(cfg))
        nets = {role: DiffNetConfig("mmdit", **net_args(cfg, role))
                for role in ROLES}
        family = Family(spec=spec, large_cfg=nets["large"],
                        small_cfg=nets["small"],
                        large_params=params["large"],
                        small_params=params["small"])
        by_label = {arm.label: arm for arm in ARMS}
        self.arms = {}
        for entry in cfg["arms"]:
            arm = by_label[entry["label"]]
            if (arm.idx != entry["idx"]
                    or arm.relay_step != entry["relay_step"]
                    or arm.program.segments[-1].stop != cfg["steps"]):
                raise ValueError(f"arm {entry} is not the program's "
                                 f"{arm.label} (idx {arm.idx})")
            self.arms[arm.label] = arm
        self.executor = Executor({"F3": family})
