"""The MMDiT block's configurable parts: 2×2 patches, the head count, and the
default configuration, which computes what the trained families were trained
on."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import diffusion_nets as dn


@pytest.mark.parametrize("p,hw", [(1, 8), (2, 8), (2, 128), (4, 16)])
def test_patchify_round_trip(p, hw):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, hw, hw, 16))
    tokens = dn.patchify(x, p)
    assert tokens.shape == (2, (hw // p) ** 2, p * p * 16)
    np.testing.assert_array_equal(dn.unpatchify(tokens, hw, hw, p), x)


def test_patchify_is_the_stride_2_convolution():
    """Tokens times the patch matrix are a 2×2 stride-2 convolution whose
    HWIO kernel is that matrix reshaped (p, p, C, width)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 16))
    w = jax.random.normal(jax.random.PRNGKey(2), (2 * 2 * 16, 32))
    hi = jax.lax.Precision.HIGHEST
    got = jnp.matmul(dn.patchify(x, 2), w, precision=hi)
    conv = jax.lax.conv_general_dilated(
        x, w.reshape(2, 2, 16, 32), (2, 2), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)
    np.testing.assert_allclose(got, conv.reshape(2, 16, 32), rtol=1e-5,
                               atol=1e-5)


def _open_gates(params):
    """adaLN-Zero starts every block as the identity; give the modulations
    and the output scale small values so every block moves the output."""
    return jax.tree_util.tree_map(
        lambda a: a + 0.02 * jnp.cos(jnp.arange(a.size).reshape(a.shape)),
        params)


def test_output_changes_with_heads():
    """``DiffNetConfig.heads`` reaches the attention: the same weights in 2, 4
    or 8 heads give different outputs."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 4))
    cond = jax.random.normal(jax.random.PRNGKey(4), (2, 16))
    base = dn.DiffNetConfig("mmdit", width=64, depth=2)
    params = _open_gates(dn.init_net(jax.random.PRNGKey(5), base))
    outs = [np.asarray(jax.jit(partial(
        dn.mmdit_apply, cfg=dn.DiffNetConfig("mmdit", width=64, depth=2,
                                             heads=h)))(params, x, 0.5, cond))
            for h in (2, 4, 8)]
    for a in range(3):
        for b in range(a):
            rel = np.linalg.norm(outs[a] - outs[b]) / np.linalg.norm(outs[b])
            assert rel > 1e-3


def _mmdit_apply_4_heads(params, x, t, cond):
    """``mmdit_apply`` as it was before the block became configurable: 4
    heads, 1×1 patches, no qk norm, no MMDiT-X layer."""
    b, hh, ww, c = x.shape
    w = params["patch"].shape[1]
    img = x.reshape(b, hh * ww, c) @ params["patch"] + params["pos"][None]
    txt = (cond @ params["txt_proj"]).reshape(b, -1, w)
    temb = (dn.time_embed(jnp.broadcast_to(t, (b,)), 64) @ params["t_emb"]
            + cond @ params["c_emb"])
    for lp in params["layers"]:
        mi = jax.nn.silu(temb) @ lp["ada_img"]
        mt = jax.nn.silu(temb) @ lp["ada_txt"]
        si1, sc1, g1, si2, sc2, g2 = jnp.split(mi, 6, -1)
        ti1, tc1, tg1, ti2, tc2, tg2 = jnp.split(mt, 6, -1)
        img_n = dn._modulate(img, si1, sc1)
        txt_n = dn._modulate(txt, ti1, tc1)
        qi, ki, vi = jnp.split(img_n @ lp["qkv_img"], 3, -1)
        qt, kt, vt = jnp.split(txt_n @ lp["qkv_txt"], 3, -1)
        k = jnp.concatenate([ki, kt], 1)
        v = jnp.concatenate([vi, vt], 1)
        ai = dn.joint_attention(qi, k, v, 4)
        at = dn.joint_attention(qt, k, v, 4)
        img = img + g1[:, None] * (ai @ lp["o_img"])
        txt = txt + tg1[:, None] * (at @ lp["o_txt"])
        img_n = dn._modulate(img, si2, sc2)
        txt_n = dn._modulate(txt, ti2, tc2)
        img = img + g2[:, None] * (
            jax.nn.gelu(img_n @ lp["mlp1_img"]) @ lp["mlp2_img"])
        txt = txt + tg2[:, None] * (
            jax.nn.gelu(txt_n @ lp["mlp1_txt"]) @ lp["mlp2_txt"])
    out = dn._ln(img) * (1 + params["out_norm"])
    return (out @ params["out"]).reshape(b, hh, ww, c)


@pytest.mark.parametrize("name", ["F3_LARGE", "F3_MID", "F3_SMALL"])
def test_defaults_compute_the_4_head_block_to_the_bit(name):
    """The trained families' configurations (defaults: 4 heads, 1×1
    patches) and no configuration at all compute the 4-head block exactly:
    outputs and gradients, bit for bit."""
    cfg = getattr(dn, name)
    params = _open_gates(dn.init_net(jax.random.PRNGKey(6), cfg))
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 8, 8, 4))
    cond = jax.random.normal(jax.random.PRNGKey(8), (3, 16))
    run = jax.jit(lambda f, p: f(p), static_argnums=0)
    fns = (lambda p: dn.apply_net(p, cfg, x, 0.4, cond),
           lambda p: dn.mmdit_apply(p, x, 0.4, cond),
           lambda p: _mmdit_apply_4_heads(p, x, 0.4, cond))
    want = run(fns[-1], params)
    for f in fns[:-1]:
        np.testing.assert_array_equal(run(f, params), want)
    grads = [run(jax.grad(lambda p, f=f: (f(p) ** 2).sum()), params)
             for f in (fns[0], fns[-1])]
    for g, w in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_array_equal(g, w)


def _rms_heads_per_head(x, scale, heads):
    """qk-RMSNorm as the per-head formula: (B, N, heads, dh) views."""
    b, n, w = x.shape
    xh = x.reshape(b, n, heads, w // heads)
    xh = xh * jax.lax.rsqrt(jnp.mean(jnp.square(xh), -1, keepdims=True)
                            + 1e-6) * scale
    return xh.reshape(b, n, w)


@pytest.mark.parametrize("width,heads", [(2432, 38), (1536, 24)],
                         ids=["large", "medium"])
def test_rms_heads_is_the_per_head_formula(width, heads):
    """The lane-dense qk-RMSNorm equals the per-head formula in float32 at
    SD3.5's published widths (heads of 64), with rows of very different
    magnitudes and a non-unit scale."""
    kx, km, ks = jax.random.split(jax.random.PRNGKey(9), 3)
    mag = 10.0 ** jax.random.uniform(km, (2, 37, 1), minval=-2, maxval=2)
    x = jax.random.normal(kx, (2, 37, width)) * mag
    scale = 1.5 + 0.25 * jax.random.normal(ks, (width // heads,))
    got = jax.jit(dn._rms_heads, static_argnums=2)(x, scale, heads)
    want = jax.jit(_rms_heads_per_head, static_argnums=2)(x, scale, heads)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=0)


def test_qk_norm_gradient_is_the_per_head_formulas(monkeypatch):
    """``mmdit/qk_norm`` is trained: the gradient of a block with heads of
    64, qk-RMSNorm and an MMDiT-X layer matches the per-head formula's,
    every parameter to a relative error of 1e-5."""
    cfg = dn.DiffNetConfig("mmdit", width=128, depth=2, heads=2,
                           patch=2, qk_norm=True, dual_layers=(0,))
    params = _open_gates(dn.init_net(jax.random.PRNGKey(6), cfg))
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 8, 8, 4))
    cond = jax.random.normal(jax.random.PRNGKey(8), (3, 16))

    def grad():
        return jax.jit(jax.grad(lambda p: (dn.mmdit_apply(
            p, x, 0.4, cond, cfg) ** 2).sum()))(params)

    got = grad()
    monkeypatch.setattr(dn, "_rms_heads", _rms_heads_per_head)
    want = grad()
    for g, w in zip(*map(jax.tree_util.tree_leaves, (got, want))):
        assert np.linalg.norm(g - w) <= 1e-5 * np.linalg.norm(w)
