"""Plain reference of the SD3.5 Large → Medium rectified-flow relay at the
published block, in float32 jax.numpy.

It imports nothing of the system under test. It follows diffusers'
``SD3Transformer2DModel`` with ``JointTransformerBlock``: a 2×2 stride-2
patch convolution; per block, adaLN with six modulations per stream, joint
attention over image and text tokens in heads of 64 with ``RMSNorm(64,
eps=1e-6)`` applied per head to each stream's q and k, a tanh-GELU MLP of 4×
width; in an MMDiT-X block (``use_dual_attention``, adaLN
``SD35AdaLayerNormZeroX``) nine image modulations and a second, image-only
self-attention with its own projections and qk norms, computed from the
block's input and added beside the joint one; the output projection
unpatchified as ``"nhwpqc->nchpwq"``. The departures listed in the
configuration file hold here too (synthetic conditioning, Fourier timestep
embedding, no adaLN on the output layer, a position table, x0
parameterisation). The relay, the sampler, each request's prompt embedding
and starting noise are those of ``mmdit_relay_ref``.

Every matmul runs at ``Precision.HIGHEST``; attention runs one head at a
time so that its scores fit beside the weights. With ``fp8=True`` it is the
control: every matmul operand is first rounded to float8 e4m3 with a
per-tensor scale (q, k and v as whole tensors, the probabilities per head),
the precision below the configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from harness import load_module

_base = load_module(Path(__file__).with_name("mmdit_relay_ref.py"))
prompt_embedding = _base.prompt_embedding
start_noise = _base.start_noise


def _rms_norm(x, scale):
    """RMSNorm over the last axis (a head's 64 values), eps 1e-6."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + 1e-6) * scale


def x0_prediction(p, x, t, cond, heads: int, patch: int, dual_layers,
                  fp8: bool = False):
    """The MMDiT's clean-latent prediction. x: (B, H, W, C); t: scalar RF
    time; cond: (B, 16); ``heads`` heads of width / heads; ``dual_layers``
    the indices of the MMDiT-X blocks."""
    mm = partial(_base._einsum, fp8=fp8)
    rnd = _base._round_fp8 if fp8 else (lambda a: a)
    b, hh, ww, c = x.shape
    w = p["patch"].shape[1]
    dh = w // heads
    hp, wp = hh // patch, ww // patch
    kernel = p["patch"].reshape(patch, patch, c, w)  # HWIO
    img = jax.lax.conv_general_dilated(
        rnd(x), rnd(kernel), (patch, patch), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST).reshape(b, hp * wp, w)
    img = img + p["pos"]
    txt = mm("bc,cw->bw", cond, p["txt_proj"]).reshape(b, -1, w)
    freqs = jnp.exp(jnp.linspace(0.0, 4.0, 32))
    ang = jnp.log1p(jnp.full((b,), t, jnp.float32))[:, None] * freqs[None]
    fourier = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    temb = mm("bf,fw->bw", fourier, p["t_emb"]) + mm("bc,cw->bw", cond,
                                                      p["c_emb"])

    def project(a, weight, lp, stream):
        """q, k, v of a stream in heads, q and k RMS-normalised per head."""
        qkv = mm("bnw,wk->bnk", a, weight)
        q, k, v = (qkv[..., j * w:(j + 1) * w].reshape(b, a.shape[1], heads,
                                                       dh) for j in range(3))
        return (_rms_norm(q, lp[f"q_norm_{stream}"]),
                _rms_norm(k, lp[f"k_norm_{stream}"]), v)

    def attention(q, k, v):
        """Softmax attention of q (B, N, h, d) over k, v (B, M, h, d), one
        head at a time."""
        def one(head):
            qh, kh, vh = head
            s = jnp.einsum("bnd,bmd->bnm", qh, kh,
                           precision=jax.lax.Precision.HIGHEST) / np.sqrt(dh)
            return jnp.einsum("bnm,bmd->bnd", rnd(_base._softmax(s)), vh,
                              precision=jax.lax.Precision.HIGHEST)

        by_head = [rnd(a).transpose(2, 0, 1, 3) for a in (q, k, v)]
        o = jax.lax.map(one, tuple(by_head))  # (h, B, N, d)
        return o.transpose(1, 2, 0, 3).reshape(b, q.shape[1], w)

    def modulate(a, shift, scale):
        return _base._layernorm(a) * (1.0 + scale[:, None]) + shift[:, None]

    for i, lp in enumerate(p["layers"]):
        ci = mm("bw,wk->bk", _base._silu(temb), lp["ada_img"])
        ct = mm("bw,wk->bk", _base._silu(temb), lp["ada_txt"])
        chunks_i = [ci[:, j * w:(j + 1) * w] for j in range(ci.shape[1] // w)]
        shift_i1, scale_i1, gate_i1, shift_i2, scale_i2, gate_i2 = chunks_i[:6]
        shift_t1, scale_t1, gate_t1, shift_t2, scale_t2, gate_t2 = (
            ct[:, j * w:(j + 1) * w] for j in range(6))
        q_i, k_i, v_i = project(modulate(img, shift_i1, scale_i1),
                                lp["qkv_img"], lp, "img")
        q_t, k_t, v_t = project(modulate(txt, shift_t1, scale_t1),
                                lp["qkv_txt"], lp, "txt")
        k = jnp.concatenate([k_i, k_t], 1)
        v = jnp.concatenate([v_i, v_t], 1)
        att_i = mm("bnw,wk->bnk", attention(q_i, k, v), lp["o_img"])
        att_t = mm("bnw,wk->bnk", attention(q_t, k, v), lp["o_txt"])
        new_img = img + gate_i1[:, None] * att_i
        if i in dual_layers:
            shift_x, scale_x, gate_x = chunks_i[6:]
            q_x, k_x, v_x = project(modulate(img, shift_x, scale_x),
                                    lp["qkv_x"], lp, "x")
            att_x = mm("bnw,wk->bnk", attention(q_x, k_x, v_x), lp["o_x"])
            new_img = new_img + gate_x[:, None] * att_x
        img = new_img
        txt = txt + gate_t1[:, None] * att_t
        h_i = _base._gelu_tanh(mm("bnw,wk->bnk",
                                  modulate(img, shift_i2, scale_i2),
                                  lp["mlp1_img"]))
        h_t = _base._gelu_tanh(mm("bnw,wk->bnk",
                                  modulate(txt, shift_t2, scale_t2),
                                  lp["mlp1_txt"]))
        img = img + gate_i2[:, None] * mm("bnk,kw->bnw", h_i, lp["mlp2_img"])
        txt = txt + gate_t2[:, None] * mm("bnk,kw->bnw", h_t, lp["mlp2_txt"])

    out = mm("bnw,wk->bnk", _base._layernorm(img) * (1.0 + p["out_norm"]),
             p["out"])
    out = out.reshape(b, hp, wp, patch, patch, c)
    return jnp.einsum("nhwpqc->nhpwqc", out).reshape(b, hh, ww, c)


@partial(jax.jit, static_argnames=("heads", "patch", "dual_layers", "fp8"))
def _euler_step(p, x, t, t_next, cond, heads, patch, dual_layers, fp8):
    x0 = x0_prediction(p, x, t, cond, heads, patch, dual_layers, fp8)
    v = (x - x0) / jnp.maximum(t, 1e-3)
    return x + (t_next - t) * v


def _role(cfg: dict, role: str) -> dict:
    r = cfg if role == "large" else cfg["medium"]
    return {"heads": r["num_attention_heads"], "patch": r["patch_size"],
            "dual_layers": tuple(r.get("dual_attention_layers", ()))}


def generate(cfg: dict, params, requests, fp8: bool = False) -> np.ndarray:
    """Final latents of ``requests`` [(arm label, prompt seed)], one request
    at a time: the large model for ladder steps [0, s), then the medium
    model from its sigma-matched entry to the end of the 50-step linear
    ladder."""
    arms = {a["label"]: a for a in cfg["arms"]}
    shape = (cfg["sample_size"], cfg["sample_size"], cfg["in_channels"])
    steps = cfg["steps"]
    times = np.asarray(jnp.linspace(1.0, 0.0, steps + 1).astype(jnp.float32))
    large, small = _role(cfg, "large"), _role(cfg, "small")
    outs = []
    for label, seed in requests:
        arm = arms[label]
        s = arm["relay_step"]
        entry = int(np.argmin(np.abs(times[:-1] - times[s])))
        x = start_noise(arm["idx"], seed, shape)[None]
        cond = jnp.asarray(prompt_embedding(seed))[None]
        with jax.default_matmul_precision("highest"):
            for i in range(s):
                x = _euler_step(params["large"], x, times[i], times[i + 1],
                                cond, fp8=fp8, **large)
            for i in range(entry, steps):
                x = _euler_step(params["small"], x, times[i], times[i + 1],
                                cond, fp8=fp8, **small)
        outs.append(np.asarray(x[0]))
    return np.stack(outs)
