"""Diffusion denoiser backbones for the two RISE relay families:

* ``unet``  — conv UNet with FiLM conditioning (family "XL", SDXL/Vega).
* ``mmdit`` — two-stream MMDiT (joint image+text-token attention, per-modality
  adaLN), x0-parameterised (family "F3", SD3.5 Large/Medium).  A
  :class:`DiffNetConfig` sets its head count, patch size, qk-RMSNorm and
  the MMDiT-X layers (a second, image-only attention), so the same code runs
  the trained small families and SD3.5's published block.

Large/small variants share a latent space within a family, the property
relay inference exploits.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import head_group
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
    patch: int = 1  # mmdit: p×p latent patches per image token
    qk_norm: bool = False  # mmdit: RMSNorm of each head's q and k
    dual_layers: tuple = ()  # mmdit: MMDiT-X layers (image-only attention)


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
    n_img = (cfg.latent_hw // cfg.patch) ** 2
    patch_dim = cfg.patch * cfg.patch * cfg.latent_ch
    dh = w // cfg.heads

    def layer(i):
        dual = i in cfg.dual_layers
        lp = {
            # adaLN-Zero (DiT): modulations/gates start at zero so every
            # block begins as identity — random gates at this scale never
            # learn the conditional map (see EXPERIMENTS.md §Repro notes).
            # An MMDiT-X layer's image stream has three more: shift, scale
            # and gate of its image-only attention.
            "ada_img": jnp.zeros((w, (9 if dual else 6) * w), jnp.float32),
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
        streams = ("img", "txt")
        if dual:
            lp["qkv_x"] = _dense_init(next(ks), w, 3 * w)
            lp["o_x"] = _dense_init(next(ks), w, w)
            streams += ("x",)
        if cfg.qk_norm:  # RMSNorm scales of each stream's q and k heads
            for s in streams:
                lp[f"q_norm_{s}"] = jnp.ones((dh,), jnp.float32)
                lp[f"k_norm_{s}"] = jnp.ones((dh,), jnp.float32)
        return lp

    return {
        "patch": _dense_init(next(ks), patch_dim, w),
        "pos": jax.random.normal(next(ks), (n_img, w), jnp.float32) * 0.02,
        "txt_proj": _dense_init(next(ks), cfg.cond_dim, cfg.text_tokens * w),
        "t_emb": _dense_init(next(ks), 64, w),
        # pooled-conditioning path into adaLN (SD3 conditions the modulation
        # on [timestep; pooled text embedding] — without it the joint
        # attention alone is too weak a pathway at this scale)
        "c_emb": _dense_init(next(ks), cfg.cond_dim, w),
        "layers": [layer(i) for i in range(d)],
        "out_norm": jnp.zeros((w,), jnp.float32),
        "out": _dense_init(next(ks), w, patch_dim),
    }


def _ln(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-6)


def _modulate(x, shift, scale):
    return _ln(x) * (1 + scale[:, None]) + shift[:, None]


def _rms_heads(x, scale, heads: int):
    """qk-RMSNorm: each head's slice of ``x`` (B, N, heads·dh) divided by
    its root mean square over dh (eps 1e-6), times ``scale`` (dh,).

    Lane-dense: ``x`` keeps its (B, N, width) shape.  The per-head sums of
    squares are a matmul with the 0/1 head indicator E (width, heads) and
    the rsqrt returns to the width through E.T, both at ``HIGHEST`` (f32
    statistics).  A (…, heads, dh) view would put dh = 64 in the lane
    dimension, which the TPU pads to 128 and relays through HBM in f32;
    this way XLA reads ``x`` for the statistics and again in the
    multiply, into which the consumer's cast to bf16 fuses."""
    w = x.shape[-1]
    dh = w // heads
    e = (jnp.arange(w)[:, None] // dh == jnp.arange(heads)).astype(x.dtype)
    hi = jax.lax.Precision.HIGHEST
    ms = jnp.matmul(jnp.square(x), e, precision=hi) / dh
    r = jnp.matmul(jax.lax.rsqrt(ms + 1e-6), e.T, precision=hi)
    return x * r * jnp.tile(scale, heads)


def patchify(x: Array, p: int) -> Array:
    """(B, H, W, C) → (B, H/p·W/p, p·p·C): one token per p×p patch, its
    features in the (row, column, channel) order of a p×p stride-p
    convolution's kernel, tokens in row-major patch order."""
    b, hh, ww, c = x.shape
    x = x.reshape(b, hh // p, p, ww // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * c)


def unpatchify(tokens: Array, hh: int, ww: int, p: int) -> Array:
    """The inverse of :func:`patchify`: (B, H/p·W/p, p·p·C) → (B, H, W, C)."""
    b = tokens.shape[0]
    c = tokens.shape[-1] // (p * p)
    x = tokens.reshape(b, hh // p, ww // p, p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, hh, ww, c)


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


def attention_blocks(n_q: int, n_k: int, heads: int, head_dim: int) -> tuple:
    """(block_q, block_k) of the flash kernel for ``n_q`` queries over
    ``n_k`` keys in ``heads`` heads of ``head_dim``, the fastest tried on a
    v5e.  On the kernel's head-group grid (SD3.5's 38 and 24 heads of 64):
    1024 queries over every key in one block, up to 4608 keys (over 4429 or
    4096 keys at widths 2432 and 1536, 1024×1024 blocks took 12–20% longer,
    and 512×512 twice as long in an earlier version of the kernel).  Every
    head in one step (heads of 608 and 384): 512×512."""
    if head_group(heads, heads, head_dim, False, None, None):
        keys = -(-n_k // 128) * 128
        return 1024, (keys if keys <= 4608 else 1024)
    return 512, 512


def joint_attention_flash(q: Array, k: Array, v: Array, heads: int, *,
                          block_q: int = None, block_k: int = None,
                          interpret: bool = False) -> Array:
    """:func:`joint_attention_xla` as one Pallas flash-attention call on the
    (B, tokens, width) layout: the running max, sum and output stay in VMEM,
    and neither a score nor a head transpose reaches HBM.  Operands enter
    the MXU as bf16 (as XLA's default precision rounds the f32 einsums),
    scores, softmax statistics and accumulation stay f32, and the
    probabilities enter PV as bf16.  Blocks default to
    :func:`attention_blocks` of the shapes."""
    bq, bk = attention_blocks(q.shape[1], k.shape[1], heads,
                              q.shape[2] // heads)
    bf16 = jnp.bfloat16
    out = flash_attention_packed(
        q.astype(bf16), k.astype(bf16), v.astype(bf16), heads=heads,
        causal=False, block_q=block_q or bq, block_k=block_k or bk,
        interpret=interpret)
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
    """x: (B,H,W,C) latent; t: RF time; cond: (B,cond_dim) → x̂0 (B,H,W,C).
    ``cfg`` (default: ``DiffNetConfig("mmdit")``) gives the head count, the
    patch size, qk-RMSNorm and the MMDiT-X layers, whose image stream adds a
    self-attention of its own (SD3.5 Medium's ``use_dual_attention``
    block: adaLN with nine modulations, the second attention from the
    block's input beside the joint one).
    Named scopes mark its parts in the compiled program's metadata:
    ``mmdit/embed``, per block ``mmdit/{adaln,qkv,qk_norm,attention,
    attn_out,mlp}`` and in MMDiT-X blocks ``mmdit/{qkv_x,attention_x,
    attn_out_x}`` (no block index, so the blocks add up) and
    ``mmdit/final``."""
    cfg = cfg or DiffNetConfig("mmdit")
    b, hh, ww, c = x.shape
    w = params["patch"].shape[1]
    heads, p = cfg.heads, cfg.patch
    with jax.named_scope("embed"):
        img = patchify(x, p) @ params["patch"] + params["pos"][None]
        txt = (cond @ params["txt_proj"]).reshape(b, -1, w)
        temb = (
            time_embed(jnp.broadcast_to(t, (b,)), 64) @ params["t_emb"]
            + cond @ params["c_emb"]
        )  # (B,w) — [timestep; pooled conditioning]

    @jax.named_scope("attention")
    def attn_joint(q, k, v):
        return joint_attention(q, k, v, heads)

    @jax.named_scope("attention_x")
    def attn_image(q, k, v):
        return joint_attention(q, k, v, heads)

    def qk_norm(lp, stream, q, k):
        if not cfg.qk_norm:
            return q, k
        with jax.named_scope("qk_norm"):
            return (_rms_heads(q, lp[f"q_norm_{stream}"], heads),
                    _rms_heads(k, lp[f"k_norm_{stream}"], heads))

    for i, lp in enumerate(params["layers"]):
        dual = i in cfg.dual_layers
        with jax.named_scope("adaln"):
            mi = jax.nn.silu(temb) @ lp["ada_img"]
            mt = jax.nn.silu(temb) @ lp["ada_txt"]
            si1, sc1, g1, si2, sc2, g2, *mx = jnp.split(mi, mi.shape[-1] // w, -1)
            ti1, tc1, tg1, ti2, tc2, tg2 = jnp.split(mt, 6, -1)

        with jax.named_scope("qkv"):
            img_n = _modulate(img, si1, sc1)
            txt_n = _modulate(txt, ti1, tc1)
            qi, ki, vi = jnp.split(img_n @ lp["qkv_img"], 3, -1)
            qt, kt, vt = jnp.split(txt_n @ lp["qkv_txt"], 3, -1)
        qi, ki = qk_norm(lp, "img", qi, ki)
        qt, kt = qk_norm(lp, "txt", qt, kt)
        with jax.named_scope("qkv"):
            k = jnp.concatenate([ki, kt], 1)
            v = jnp.concatenate([vi, vt], 1)
        if dual:  # from the block's input, as the joint attention
            sx, scx, gx = mx
            with jax.named_scope("qkv_x"):
                qx, kx, vx = jnp.split(_modulate(img, sx, scx) @ lp["qkv_x"],
                                       3, -1)
            qx, kx = qk_norm(lp, "x", qx, kx)
            ax = attn_image(qx, kx, vx)
        ai = attn_joint(qi, k, v)
        at = attn_joint(qt, k, v)
        with jax.named_scope("attn_out"):
            img = img + g1[:, None] * (ai @ lp["o_img"])
            txt = txt + tg1[:, None] * (at @ lp["o_txt"])
        if dual:
            with jax.named_scope("attn_out_x"):
                img = img + gx[:, None] * (ax @ lp["o_x"])

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
        return unpatchify(out @ params["out"], hh, ww, p)


def init_net(key, cfg: DiffNetConfig) -> dict:
    return init_unet(key, cfg) if cfg.kind == "unet" else init_mmdit(key, cfg)


def apply_net(params, cfg: DiffNetConfig, x, t, cond):
    if cfg.kind == "unet":
        return unet_apply(params, x, t, cond)
    return mmdit_apply(params, x, t, cond, cfg)
