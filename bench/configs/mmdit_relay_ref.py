"""Plain reference of the MMDiT rectified-flow relay, in float32 jax.numpy.

It imports nothing of the system under test. It follows the SD3 MMDiT block
(joint attention over image and text tokens, per-stream adaLN with six
modulations, tanh-GELU MLP of 4x width) with the departures listed in the
configuration files, among them the head layout ``heads_as_run``, and the
rectified-flow Euler sampler over the 50-step linear ladder: the large
model runs steps [0, s), the medium model finishes
from its sigma-matched entry. Each request's prompt embedding and starting
noise follow the serving contract: the noise is the standard normal drawn
from ``fold_in(PRNGKey(arm_idx * 7919), prompt_seed)``.

Every matmul runs at ``Precision.HIGHEST``. With ``fp8=True`` it is the
control: every matmul operand is first rounded to float8 e4m3 with a
per-tensor scale, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# the synthetic prompt: 8 scene features mapped to 12 renderer parameters
_PROJ = np.random.default_rng(1234).normal(size=(8, 12)).astype(np.float32)


def prompt_embedding(seed: int) -> np.ndarray:
    """The 16-dim conditioning vector of one prompt seed (family F3)."""
    rng = np.random.default_rng(seed)
    content = rng.normal(size=8).astype(np.float32)
    complexity = float(rng.uniform())
    wants_text = bool(rng.uniform() < 0.35)
    phase = rng.uniform(0, 2 * np.pi, size=2).astype(np.float32)
    e = np.zeros(16, np.float32)
    e[:12] = np.tanh(content @ _PROJ)
    e[12] = complexity
    flag = 1.0 if wants_text else 0.0
    e[13] = flag
    e[14] = flag * np.sin(phase[0])
    e[15] = flag * np.cos(phase[0])
    return e


def start_noise(arm_idx: int, prompt_seed: int, latent_shape) -> jax.Array:
    key = jax.random.fold_in(jax.random.PRNGKey(arm_idx * 7919), prompt_seed)
    return jax.random.normal(key, tuple(latent_shape), jnp.float32)


def _round_fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec, a, b, fp8: bool):
    if fp8:
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _layernorm(x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softmax(x):
    x = x - jnp.max(x, -1, keepdims=True)
    e = jnp.exp(x)
    return e / jnp.sum(e, -1, keepdims=True)


def x0_prediction(p, x, t, cond, heads: int, fp8: bool = False):
    """The MMDiT's clean-latent prediction. x: (B, H, W, C); t: scalar RF
    time; cond: (B, 16); ``heads`` heads of width / heads."""
    mm = partial(_einsum, fp8=fp8)
    b, hh, ww, c = x.shape
    w = p["patch"].shape[1]
    dh = w // heads
    img = mm("bnc,cw->bnw", x.reshape(b, hh * ww, c), p["patch"]) + p["pos"]
    txt = mm("bc,cw->bw", cond, p["txt_proj"]).reshape(b, -1, w)
    freqs = jnp.exp(jnp.linspace(0.0, 4.0, 32))
    ang = jnp.log1p(jnp.full((b,), t, jnp.float32))[:, None] * freqs[None]
    fourier = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    temb = mm("bf,fw->bw", fourier, p["t_emb"]) + mm("bc,cw->bw", cond,
                                                      p["c_emb"])

    def split(a):
        return a.reshape(b, a.shape[1], heads, dh)

    def joint_attention(q, k, v):
        s = mm("bnhd,bmhd->bhnm", split(q), split(k)) / np.sqrt(dh)
        o = mm("bhnm,bmhd->bnhd", _softmax(s), split(v))
        return o.reshape(b, q.shape[1], w)

    def modulate(a, shift, scale):
        return _layernorm(a) * (1.0 + scale[:, None]) + shift[:, None]

    for lp in p["layers"]:
        ci = mm("bw,wk->bk", _silu(temb), lp["ada_img"])
        ct = mm("bw,wk->bk", _silu(temb), lp["ada_txt"])
        shift_i1, scale_i1, gate_i1, shift_i2, scale_i2, gate_i2 = (
            ci[:, j * w:(j + 1) * w] for j in range(6))
        shift_t1, scale_t1, gate_t1, shift_t2, scale_t2, gate_t2 = (
            ct[:, j * w:(j + 1) * w] for j in range(6))
        qkv_i = mm("bnw,wk->bnk", modulate(img, shift_i1, scale_i1),
                   lp["qkv_img"])
        qkv_t = mm("bnw,wk->bnk", modulate(txt, shift_t1, scale_t1),
                   lp["qkv_txt"])
        q_i, k_i, v_i = (qkv_i[..., j * w:(j + 1) * w] for j in range(3))
        q_t, k_t, v_t = (qkv_t[..., j * w:(j + 1) * w] for j in range(3))
        k = jnp.concatenate([k_i, k_t], 1)
        v = jnp.concatenate([v_i, v_t], 1)
        att_i = mm("bnw,wk->bnk", joint_attention(q_i, k, v), lp["o_img"])
        att_t = mm("bnw,wk->bnk", joint_attention(q_t, k, v), lp["o_txt"])
        img = img + gate_i1[:, None] * att_i
        txt = txt + gate_t1[:, None] * att_t
        h_i = _gelu_tanh(mm("bnw,wk->bnk", modulate(img, shift_i2, scale_i2),
                            lp["mlp1_img"]))
        h_t = _gelu_tanh(mm("bnw,wk->bnk", modulate(txt, shift_t2, scale_t2),
                            lp["mlp1_txt"]))
        img = img + gate_i2[:, None] * mm("bnk,kw->bnw", h_i, lp["mlp2_img"])
        txt = txt + gate_t2[:, None] * mm("bnk,kw->bnw", h_t, lp["mlp2_txt"])

    out = _layernorm(img) * (1.0 + p["out_norm"])
    return mm("bnw,wc->bnc", out, p["out"]).reshape(b, hh, ww, c)


@partial(jax.jit, static_argnames=("heads", "fp8"))
def _euler_step(p, x, t, t_next, cond, heads, fp8):
    x0 = x0_prediction(p, x, t, cond, heads, fp8)
    v = (x - x0) / jnp.maximum(t, 1e-3)
    return x + (t_next - t) * v


def relay_sample(p_large, p_small, x, cond, relay_step: int, steps: int,
                 heads: int, fp8: bool = False) -> jax.Array:
    """Relay one batch: large for ladder steps [0, s), then the medium
    model from the sigma-matched entry s' to the end of its ladder."""
    times = np.asarray(jnp.linspace(1.0, 0.0, steps + 1).astype(jnp.float32))
    # Eq. 4: the medium model enters where its ladder is nearest t_s
    entry = int(np.argmin(np.abs(times[:-1] - times[relay_step])))
    with jax.default_matmul_precision("highest"):
        for i in range(relay_step):
            x = _euler_step(p_large, x, times[i], times[i + 1], cond, heads,
                            fp8)
        for i in range(entry, steps):
            x = _euler_step(p_small, x, times[i], times[i + 1], cond, heads,
                            fp8)
    return x


def generate(cfg: dict, params, requests, fp8: bool = False) -> np.ndarray:
    """Final latents of ``requests`` [(arm label, prompt seed)], one request
    at a time so that the reference fits beside the weights. ``cfg`` is the
    configuration file's content: sizes, ladder length and the arms."""
    arms = {a["label"]: a for a in cfg["arms"]}
    shape = (cfg["latent_hw"], cfg["latent_hw"], cfg["in_channels"])
    outs = []
    for label, seed in requests:
        arm = arms[label]
        x = start_noise(arm["idx"], seed, shape)[None]
        cond = jnp.asarray(prompt_embedding(seed))[None]
        y = relay_sample(params["large"], params["small"], x, cond,
                         arm["relay_step"], cfg["steps"], cfg["heads_as_run"],
                         fp8)
        outs.append(np.asarray(y[0]))
    return np.stack(outs)
