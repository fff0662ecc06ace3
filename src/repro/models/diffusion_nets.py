"""Diffusion denoiser backbones for the two RISE relay families (laptop-scale
stand-ins for SDXL/Vega and SD3.5-L/M that preserve the architectural split):

* ``unet``  — conv UNet with FiLM conditioning, ε-prediction (family "XL").
* ``mmdit`` — two-stream MMDiT (joint image+text-token attention, per-modality
  adaLN), velocity prediction (family "F3").

Large/small variants differ in width/depth only → shared latent space within
a family, exactly the property relay inference exploits.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ops import flash_attention_packed

Array = jax.Array


@dataclass(frozen=True)
class DiffNetConfig:
    kind: str  # unet | mmdit
    width: int = 48
    depth: int = 2  # res blocks per level (unet) / transformer layers (mmdit)
    heads: int = 4
    latent_hw: int = 8
    latent_ch: int = 4
    cond_dim: int = 16
    text_tokens: int = 4  # mmdit text-stream length


# configurations mirroring the paper's four models (sized for 1-core CPU)
XL_LARGE = DiffNetConfig("unet", width=32, depth=2)  # "SDXL"
XL_SMALL = DiffNetConfig("unet", width=16, depth=1)  # "Segmind-Vega"
F3_LARGE = DiffNetConfig("mmdit", width=64, depth=3)  # "SD3.5 Large"
F3_SMALL = DiffNetConfig("mmdit", width=32, depth=2)  # "SD3.5 Medium"
# mid-size cascade stages (N-hop relay programs): capacity between the
# family's large and small scales, same latent space
XL_MID = DiffNetConfig("unet", width=24, depth=2)  # "SSD-1B"-like
F3_MID = DiffNetConfig("mmdit", width=48, depth=2)  # distilled mid SD3.5


def _conv_init(key, kh, kw, cin, cout):
    scale = 1.0 / jnp.sqrt(kh * kw * cin)
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * scale


def _dense_init(key, cin, cout):
    return jax.random.normal(key, (cin, cout), jnp.float32) / jnp.sqrt(cin)


def conv2d(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def time_embed(t, dim: int) -> Array:
    """Fourier features of log-σ (or RF time)."""
    t = jnp.atleast_1d(jnp.asarray(t, jnp.float32))
    freqs = jnp.exp(jnp.linspace(0.0, 4.0, dim // 2))
    ang = jnp.log1p(t)[:, None] * freqs[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# UNet (family XL)
# ---------------------------------------------------------------------------


def init_unet(key, cfg: DiffNetConfig) -> dict:
    w, d = cfg.width, cfg.depth
    ks = iter(jax.random.split(key, 64))
    emb_dim = 4 * w

    def res_block(cin, cout):
        return {
            "conv1": _conv_init(next(ks), 3, 3, cin, cout),
            "conv2": _conv_init(next(ks), 3, 3, cout, cout),
            # zero-init FiLM (adaLN-Zero-style): conditioning opens up
            # during training instead of randomly modulating at init
            "film": jnp.zeros((emb_dim, 2 * cout), jnp.float32),
            "skip": _conv_init(next(ks), 1, 1, cin, cout) if cin != cout else None,
        }

    return {
        "emb1": _dense_init(next(ks), 64 + cfg.cond_dim, emb_dim),
        "emb2": _dense_init(next(ks), emb_dim, emb_dim),
        # conditioning is also concatenated as broadcast input channels so
        # the stem sees it directly (FiLM alone never opens at this scale)
        "stem": _conv_init(next(ks), 3, 3, cfg.latent_ch + cfg.cond_dim, w),
        "down": [res_block(w, w) for _ in range(d)],
        "down_proj": _conv_init(next(ks), 3, 3, w, 2 * w),
        "mid": [res_block(2 * w, 2 * w) for _ in range(d)],
        "up_proj": _conv_init(next(ks), 3, 3, 2 * w, w),
        "up": [res_block(2 * w, w)] + [res_block(w, w) for _ in range(d - 1)],
        "out": _conv_init(next(ks), 3, 3, w, cfg.latent_ch),
    }


def _apply_res(p, x, emb):
    h = jax.nn.silu(conv2d(x, p["conv1"]))
    scale, shift = jnp.split(emb @ p["film"], 2, axis=-1)
    h = h * (1 + scale[:, None, None, :]) + shift[:, None, None, :]
    h = conv2d(jax.nn.silu(h), p["conv2"])
    skip = conv2d(x, p["skip"]) if p["skip"] is not None else x
    return h + skip


@jax.named_scope("unet")
def unet_apply(params: dict, x: Array, t, cond: Array) -> Array:
    """x: (B,8,8,4); t: scalar σ; cond: (B,cond_dim) → ε̂ (B,8,8,4).
    Named scopes ``unet/{embed,stem,down,mid,up,out}`` mark its stages in
    the compiled program's metadata."""
    b = x.shape[0]
    with jax.named_scope("embed"):
        te = time_embed(jnp.broadcast_to(t, (b,)), 64)
        emb = jax.nn.silu(jnp.concatenate([te, cond], -1) @ params["emb1"])
        emb = jax.nn.silu(emb @ params["emb2"])

    with jax.named_scope("stem"):
        cond_maps = jnp.broadcast_to(
            cond[:, None, None, :], (b, x.shape[1], x.shape[2], cond.shape[-1])
        )
        h = conv2d(jnp.concatenate([x, cond_maps], axis=-1), params["stem"])
    with jax.named_scope("down"):
        for rp in params["down"]:
            h = _apply_res(rp, h, emb)
        skip = h
        h = conv2d(h, params["down_proj"], stride=2)  # 8→4
    with jax.named_scope("mid"):
        for rp in params["mid"]:
            h = _apply_res(rp, h, emb)
    with jax.named_scope("up"):
        h = jax.image.resize(h, (b, 8, 8, h.shape[-1]), "nearest")
        h = conv2d(h, params["up_proj"])
        h = jnp.concatenate([h, skip], axis=-1)
        for rp in params["up"]:
            h = _apply_res(rp, h, emb)
    with jax.named_scope("out"):
        return conv2d(jax.nn.silu(h), params["out"])


# ---------------------------------------------------------------------------
# MMDiT (family F3)
# ---------------------------------------------------------------------------


def init_mmdit(key, cfg: DiffNetConfig) -> dict:
    w, d = cfg.width, cfg.depth
    ks = iter(jax.random.split(key, 16 + 12 * d))
    n_img = cfg.latent_hw * cfg.latent_hw

    def layer():
        return {
            # adaLN-Zero (DiT): modulations/gates start at zero so every
            # block begins as identity — random gates at this scale never
            # learn the conditional map (see EXPERIMENTS.md §Repro notes)
            "ada_img": jnp.zeros((w, 6 * w), jnp.float32),
            "ada_txt": jnp.zeros((w, 6 * w), jnp.float32),
            "qkv_img": _dense_init(next(ks), w, 3 * w),
            "qkv_txt": _dense_init(next(ks), w, 3 * w),
            "o_img": _dense_init(next(ks), w, w),
            "o_txt": _dense_init(next(ks), w, w),
            "mlp1_img": _dense_init(next(ks), w, 4 * w),
            "mlp2_img": _dense_init(next(ks), 4 * w, w),
            "mlp1_txt": _dense_init(next(ks), w, 4 * w),
            "mlp2_txt": _dense_init(next(ks), 4 * w, w),
        }

    return {
        "patch": _dense_init(next(ks), cfg.latent_ch, w),
        "pos": jax.random.normal(next(ks), (n_img, w), jnp.float32) * 0.02,
        "txt_proj": _dense_init(next(ks), cfg.cond_dim, cfg.text_tokens * w),
        "t_emb": _dense_init(next(ks), 64, w),
        # pooled-conditioning path into adaLN (SD3 conditions the modulation
        # on [timestep; pooled text embedding] — without it the joint
        # attention alone is too weak a pathway at this scale)
        "c_emb": _dense_init(next(ks), cfg.cond_dim, w),
        "layers": [layer() for _ in range(d)],
        "out_norm": jnp.zeros((w,), jnp.float32),
        "out": _dense_init(next(ks), w, cfg.latent_ch),
    }


def _ln(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6)


def _modulate(x, shift, scale):
    return _ln(x) * (1 + scale[:, None]) + shift[:, None]


def joint_attention_xla(q: Array, k: Array, v: Array, heads: int) -> Array:
    """Multi-head softmax attention of queries ``q`` (B,N,W) over the joint
    keys and values (B,M,W), as plain XLA ops: the (B,heads,N,M) scores are
    materialised.  The path on every platform but the TPU."""
    b, n, w = q.shape
    dh = w // heads
    qh = q.reshape(b, n, heads, dh)
    kh = k.reshape(b, k.shape[1], heads, dh)
    vh = v.reshape(b, v.shape[1], heads, dh)
    sc = jnp.einsum("bnhd,bmhd->bhnm", qh, kh) / jnp.sqrt(dh)
    pr = jax.nn.softmax(sc, -1)
    return jnp.einsum("bhnm,bmhd->bnhd", pr, vh).reshape(b, n, w)


def joint_attention_flash(q: Array, k: Array, v: Array, heads: int, *,
                          block_q: int = 512, block_k: int = 512,
                          interpret: bool = False) -> Array:
    """:func:`joint_attention_xla` as one Pallas flash-attention call on the
    (B, tokens, width) layout: the running max, sum and output stay in VMEM,
    and neither a score nor a head transpose reaches HBM.  Operands enter
    the MXU as bf16 (as XLA's default precision rounds the f32 einsums),
    scores, softmax statistics and accumulation stay f32, and the
    probabilities enter PV as bf16.  512×512 blocks were the fastest tried
    at widths 2432 and 1536 on a v5e."""
    bf16 = jnp.bfloat16
    out = flash_attention_packed(
        q.astype(bf16), k.astype(bf16), v.astype(bf16), heads=heads,
        causal=False, block_q=block_q, block_k=block_k, interpret=interpret)
    return out.astype(q.dtype)


@functools.partial(jax.custom_jvp, nondiff_argnums=(3,))
def joint_attention(q: Array, k: Array, v: Array, heads: int) -> Array:
    """The flash kernel where the program is lowered for a TPU, the XLA
    einsums elsewhere.  Derivatives are those of the einsum path on every
    platform (the kernel has none)."""
    return jax.lax.platform_dependent(
        q, k, v, tpu=functools.partial(joint_attention_flash, heads=heads),
        default=functools.partial(joint_attention_xla, heads=heads))


@joint_attention.defjvp
def _joint_attention_jvp(heads, primals, tangents):
    _, t_out = jax.jvp(functools.partial(joint_attention_xla, heads=heads),
                       primals, tangents)
    return joint_attention(*primals, heads), t_out


@jax.named_scope("mmdit")
def mmdit_apply(params: dict, x: Array, t, cond: Array, cfg: DiffNetConfig = None) -> Array:
    """x: (B,8,8,4); t: RF time; cond: (B,cond_dim) → v̂ (B,8,8,4).
    Named scopes mark its parts in the compiled program's metadata:
    ``mmdit/embed``, per block ``mmdit/{adaln,qkv,attention,attn_out,mlp}``
    (no block index, so the blocks add up) and ``mmdit/final``."""
    b, hh, ww, c = x.shape
    w = params["patch"].shape[1]
    heads = 4
    with jax.named_scope("embed"):
        img = x.reshape(b, hh * ww, c) @ params["patch"] + params["pos"][None]
        txt = (cond @ params["txt_proj"]).reshape(b, -1, w)
        temb = (
            time_embed(jnp.broadcast_to(t, (b,)), 64) @ params["t_emb"]
            + cond @ params["c_emb"]
        )  # (B,w) — [timestep; pooled conditioning]

    @jax.named_scope("attention")
    def attn_joint(q, k, v):
        return joint_attention(q, k, v, heads)

    for lp in params["layers"]:
        with jax.named_scope("adaln"):
            mi = jax.nn.silu(temb) @ lp["ada_img"]
            mt = jax.nn.silu(temb) @ lp["ada_txt"]
            si1, sc1, g1, si2, sc2, g2 = jnp.split(mi, 6, -1)
            ti1, tc1, tg1, ti2, tc2, tg2 = jnp.split(mt, 6, -1)

        with jax.named_scope("qkv"):
            img_n = _modulate(img, si1, sc1)
            txt_n = _modulate(txt, ti1, tc1)
            qi, ki, vi = jnp.split(img_n @ lp["qkv_img"], 3, -1)
            qt, kt, vt = jnp.split(txt_n @ lp["qkv_txt"], 3, -1)
            k = jnp.concatenate([ki, kt], 1)
            v = jnp.concatenate([vi, vt], 1)
        ai = attn_joint(qi, k, v)
        at = attn_joint(qt, k, v)
        with jax.named_scope("attn_out"):
            img = img + g1[:, None] * (ai @ lp["o_img"])
            txt = txt + tg1[:, None] * (at @ lp["o_txt"])

        with jax.named_scope("mlp"):
            img_n = _modulate(img, si2, sc2)
            txt_n = _modulate(txt, ti2, tc2)
            img = img + g2[:, None] * (
                jax.nn.gelu(img_n @ lp["mlp1_img"]) @ lp["mlp2_img"]
            )
            txt = txt + tg2[:, None] * (
                jax.nn.gelu(txt_n @ lp["mlp1_txt"]) @ lp["mlp2_txt"]
            )

    with jax.named_scope("final"):
        out = _ln(img) * (1 + params["out_norm"])
        return (out @ params["out"]).reshape(b, hh, ww, c)


def init_net(key, cfg: DiffNetConfig) -> dict:
    return init_unet(key, cfg) if cfg.kind == "unet" else init_mmdit(key, cfg)


def apply_net(params, cfg: DiffNetConfig, x, t, cond):
    if cfg.kind == "unet":
        return unet_apply(params, x, t, cond)
    return mmdit_apply(params, x, t, cond, cfg)
