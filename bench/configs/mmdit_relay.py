"""The MMDiT relay configurations as the system under test serves them.

Builds, from a configuration file and a seed, the two MMDiT roles in the
parameter layout of ``repro.models.diffusion_nets.mmdit_apply``, the F3
``Family`` around them, and an ``Executor`` whose ``generate_bucketed`` is
the timed path. Also counts the model FLOPs of a request from shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROLES = ("large", "small")


def role_sizes(cfg: dict) -> dict:
    """{role: (width, depth)}: the large role is the file's top level, the
    small role its ``medium`` group."""
    med = cfg["medium"]
    return {"large": (cfg["caption_projection_dim"], cfg["num_layers"]),
            "small": (med["caption_projection_dim"], med["num_layers"])}


def latent_shape(cfg: dict) -> tuple:
    return (cfg["latent_hw"], cfg["latent_hw"], cfg["in_channels"])


def _role_shapes(cfg: dict, width: int, depth: int) -> dict:
    w, c = width, cfg["in_channels"]
    layer = {
        "ada_img": (w, 6 * w), "ada_txt": (w, 6 * w),
        "qkv_img": (w, 3 * w), "qkv_txt": (w, 3 * w),
        "o_img": (w, w), "o_txt": (w, w),
        "mlp1_img": (w, 4 * w), "mlp2_img": (4 * w, w),
        "mlp1_txt": (w, 4 * w), "mlp2_txt": (4 * w, w),
    }
    return {
        "patch": (c, w),
        "pos": (cfg["latent_hw"] ** 2, w),
        "txt_proj": (cfg["cond_dim"], cfg["text_tokens"] * w),
        "t_emb": (64, w),
        "c_emb": (cfg["cond_dim"], w),
        "layers": [dict(layer) for _ in range(depth)],
        "out_norm": (w,),
        "out": (w, c),
    }


def param_shapes(cfg: dict) -> dict:
    """{role: tree of shape tuples} in the program's parameter layout."""
    return {role: _role_shapes(cfg, w, d)
            for role, (w, d) in role_sizes(cfg).items()}


def _std(name: str, shape) -> float:
    """Per-leaf standard deviation of the seeded weights. Gates and
    modulations are random (not adaLN-Zero), so every block moves the
    output; q and k get 1.5x the fan-in scale so attention is not a plain
    mean over 1357-4429 tokens."""
    fan_in = shape[0]
    if name in ("ada_img", "ada_txt"):
        return 0.5 / np.sqrt(fan_in)
    if name == "pos":
        return 0.1
    if name == "out_norm":
        return 0.1
    return 1.0 / np.sqrt(fan_in)


def _leaf(key, name, shape):
    x = jax.random.normal(key, shape, jnp.float32) * _std(name, shape)
    if name in ("qkv_img", "qkv_txt"):
        w = shape[0]
        col = jnp.concatenate([jnp.full((2 * w,), 1.5, jnp.float32),
                               jnp.ones((w,), jnp.float32)])
        x = x * col[None]
    return x


def _seed_key(seed: int) -> jax.Array:
    """A PRNG key from all 64 bits of ``seed`` (PRNGKey alone keeps 32)."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


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

    return make(_seed_key(seed))


def image_step_flops(cfg: dict, role: str) -> float:
    """Model FLOPs of one denoising step of one image: per block the
    linear layers of both streams (24 N w^2), the joint attention
    (4 N^2 w) and the two adaLN projections; plus the embeddings and the
    output projection. N counts image and text tokens. In the last block
    only the text stream's keys and values reach the output (SD3's
    ``context_pre_only`` block), so its text queries' attention, output
    projection and MLP are not counted; XLA removes them too."""
    w, depth = role_sizes(cfg)[role]
    n_img = cfg["latent_hw"] ** 2
    n_txt = cfg["text_tokens"]
    n = n_img + n_txt
    c, cond = cfg["in_channels"], cfg["cond_dim"]
    block = 24 * n * w * w + 4 * n * n * w + 2 * 2 * w * 6 * w
    last_txt_unused = n_txt * 2 * 9 * w * w + 4 * n_txt * n * w
    embed = 2 * n_img * c * w + 2 * cond * n_txt * w + 2 * 64 * w \
        + 2 * cond * w + 2 * n_img * w * c
    return float(depth * block - last_txt_unused + embed)


def request_flops(cfg: dict, relay_step: int) -> float:
    """Model FLOPs of one relay request: s large steps, then the medium
    model for the rest of the ladder."""
    steps = cfg["steps"]
    return (relay_step * image_step_flops(cfg, "large")
            + (steps - relay_step) * image_step_flops(cfg, "small"))


class System:
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
        sizes = role_sizes(cfg)
        ladder = rf_times(cfg["steps"])
        spec = FamilySpec(name="F3", kind="rf", sigmas_edge=ladder,
                          sigmas_device=ladder, sigmas_mid=ladder,
                          latent_shape=latent_shape(cfg))
        nets = {
            role: DiffNetConfig(
                "mmdit", width=w, depth=d,
                heads=cfg["heads_as_run"],
                latent_hw=cfg["latent_hw"], latent_ch=cfg["in_channels"],
                cond_dim=cfg["cond_dim"], text_tokens=cfg["text_tokens"])
            for role, (w, d) in sizes.items()
        }
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

    def distinct_shapes(self, labels) -> list:
        """One label per compiled program shape among ``labels``: arms that
        share a shape share every compiled executable."""
        first = {}
        for label in labels:
            first.setdefault(self.arms[label].program.shape_key(), label)
        return list(first.values())

    def serve(self, arm_label: str, prompt_seeds, buckets) -> np.ndarray:
        """One micro-batch through the timed path: the final latents on the
        host, one row per prompt seed."""
        return self.executor.generate_bucketed(
            self.arms[arm_label], np.asarray(prompt_seeds, np.int64),
            buckets=tuple(buckets))

    def close(self):
        self.executor = None
        self.params = None
        self.arms = {}
