"""Pallas kernel validation (interpret mode) vs pure-jnp oracles, sweeping
shapes and dtypes.  The hypothesis-based property tests skip individually
when hypothesis is absent (requirements-dev.txt); the parametrized sweeps
and regression tests always run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # property tests skip, everything else still runs
    HAVE_HYPOTHESIS = False

    def given(**kwargs):  # placeholder decorator: the test body never runs
        def deco(fn):
            return pytest.mark.skip(
                reason="property tests need hypothesis "
                "(see requirements-dev.txt)"
            )(fn)
        return deco

    def settings(**kwargs):
        return lambda fn: fn

    class _St:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _St()

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_sampler.ops import fused_cfg_step
from repro.kernels.fused_sampler.ref import ddim_coeffs, fused_cfg_step_ref
from repro.kernels.quant.ops import dequant_int8, quant_int8
from repro.kernels.quant.ref import quant_int8_ref
from repro.kernels.rglru.ops import rglru_scan
from repro.kernels.rglru.ref import rglru_scan_ref

# bf16 ulp is ~2^-8 of the magnitude; latents here reach |x| ≈ 4–5, so a
# single-rounding divergence between the f32-accumulating kernel and the
# native-bf16 oracle can hit ~0.03 on one element
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 4e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kv,s,t,d,causal,window,cap",
    [
        (2, 4, 2, 64, 64, 32, True, None, None),
        (1, 4, 4, 40, 40, 16, True, None, 50.0),  # softcap + unpadded len
        (2, 8, 2, 32, 96, 32, False, None, None),  # cross-attn style
        (1, 4, 1, 64, 64, 32, True, 16, None),  # MQA + sliding window
        (1, 2, 2, 16, 128, 64, True, None, None),  # long kv
        (2, 4, 4, 32, 40, 128, False, None, None),  # heads that fill a tile
        (1, 8, 8, 32, 40, 32, False, None, None),  # head groups, scale 1/√32
    ],
)
def test_flash_attention_vs_ref(b, h, kv, s, t, d, causal, window, cap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, t, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, t, d), dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window, softcap=cap,
        block_q=16, block_k=16, interpret=True,
    )
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@given(
    b=st.integers(1, 3), s=st.integers(2, 70), r=st.integers(1, 70),
)
@settings(max_examples=8, deadline=None)
def test_rglru_scan_property(b, s, r):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.uniform(k1, (b, s, r), minval=0.3, maxval=0.999)
    bb = jax.random.normal(k2, (b, s, r)) * 0.2
    out = rglru_scan(a, bb, block_s=16, block_r=16, interpret=True)
    ref = rglru_scan_ref(a, bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["ddim", "rf"])
@pytest.mark.parametrize("shape", [(4, 8, 8, 4), (2, 5, 7, 3), (1, 64)])
def test_fused_cfg_step(mode, shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], shape, dtype)
    ec = jax.random.normal(ks[1], shape, dtype)
    eu = jax.random.normal(ks[2], shape, dtype)
    c1, c2 = ddim_coeffs(0.4, 0.6) if mode == "ddim" else (-0.02, 0.0)
    out = fused_cfg_step(
        x, ec, eu, guidance=3.5, c1=c1, c2=c2, mode=mode, block_n=32,
        interpret=True,
    )
    ref = fused_cfg_step_ref(x, ec, eu, guidance=3.5, mode=mode, c1=c1, c2=c2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


def test_fused_ddim_matches_sampler_step():
    """The affine (c1,c2) collapse must equal the Eq. 2 two-term DDIM form."""
    from repro.core.schedules import vp_alpha_bar

    sig_t, sig_s = 2.0, 1.2
    ab_t, ab_s = float(vp_alpha_bar(sig_t)), float(vp_alpha_bar(sig_s))
    c1, c2 = ddim_coeffs(ab_t, ab_s)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (8, 16))
    eps = jax.random.normal(jax.random.PRNGKey(4), (8, 16))
    x0_hat = (x - np.sqrt(1 - ab_t) * eps) / np.sqrt(ab_t)
    ref = np.sqrt(ab_s) * x0_hat + np.sqrt(1 - ab_s) * eps
    out = fused_cfg_step(x, eps, eps, guidance=1.0, c1=c1, c2=c2,
                         mode="ddim", interpret=True, block_n=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@given(
    r=st.integers(1, 50), c=st.integers(1, 70),
    scale=st.floats(0.01, 100.0),
)
@settings(max_examples=10, deadline=None)
def test_quant_int8_roundtrip_property(r, c, scale):
    x = jax.random.normal(jax.random.PRNGKey(5), (r, c)) * scale
    q, s = quant_int8(x, interpret=True, block_r=16)
    qr, sr = quant_int8_ref(x)
    assert bool((q == qr).all())
    deq = dequant_int8(q, s, interpret=True, block_r=16)
    # error bounded by half a quantization bin per row
    bound = np.asarray(s)[..., 0] * 0.5 + 1e-7
    err = np.abs(np.asarray(deq) - np.asarray(x)).max(axis=-1)
    assert np.all(err <= bound + 1e-6)


@pytest.mark.parametrize("r", [1, 3, 17, 33])  # none divisible by block_r=16
def test_quant_int8_ragged_rows(r):
    """Regression: row counts not divisible by the block size used to trip
    an assert in the fwd fns; they now pad internally and slice back.  The
    oracle is *jitted* — that's the production parity target (XLA rewrites
    the /127 into a reciprocal multiply under jit; eager does a true IEEE
    divide, 1 ulp apart on some rows)."""
    x = jax.random.normal(jax.random.PRNGKey(7), (r, 24)) * 3.0
    q, s = quant_int8(x, interpret=True, block_r=16)
    qr, sr = jax.jit(quant_int8_ref)(x)
    assert q.shape == (r, 24) and s.shape == (r, 1)
    assert bool((q == qr).all())
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_quant_int8_zero_rows():
    """Regression: all-zero rows (amax 0) must quantize to zeros with the
    guard scale 1.0 — no NaN/inf from a 0/0 — including padded rows."""
    x = jnp.zeros((5, 12), jnp.float32)
    x = x.at[2].set(jnp.linspace(-2.0, 2.0, 12))  # one live row
    q, s = quant_int8(x, interpret=True, block_r=16)
    assert not bool(jnp.isnan(s).any()) and not bool(jnp.isinf(s).any())
    np.testing.assert_array_equal(np.asarray(s)[[0, 1, 3, 4], 0], 1.0)
    deq = dequant_int8(q, s, interpret=True, block_r=16)
    np.testing.assert_array_equal(np.asarray(deq)[[0, 1, 3, 4]], 0.0)
    qr, sr = jax.jit(quant_int8_ref)(x)
    assert bool((q == qr).all())
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_flash_attention_in_model_path():
    """Kernel output slots into the model's attention contract (B,H,S,D)."""
    b, h, kv, s, d = 1, 8, 4, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    assert out.shape == (b, h, s, d)
    assert not bool(jnp.isnan(out).any())


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("b,width,heads,n_img,n_txt,block_q,block_k", [
    (2, 608, 4, 64, 11, 32, 32),  # head dim 152, the 608 analogue
    (2, 384, 4, 64, 11, 32, 32),  # head dim 96, the 384 analogue
    (1, 384, 4, 48, 5, 16, 128),  # every key in one padded block
    (2, 384, 6, 32, 9, 16, 16),  # heads of 64, the SD3.5 layout
    (1, 2432, 38, 32, 5, 16, 16),  # SD3.5 Large's 38 heads: 19 groups
], ids=["dh152", "dh96", "one-key-block", "heads-of-64", "heads-of-64-x38"])
def test_joint_attention_flash_matches_einsum(b, width, heads, n_img, n_txt,
                                              block_q, block_k):
    """The kernel path of the MMDiT joint attention against the einsum path,
    for the image and the text queries over the joint keys (n_img + n_txt,
    not a multiple of ``block_k``).  The kernel rounds q, k, v and the
    probabilities to bf16 and keeps scores and sums f32, so against the
    einsum path on bf16-rounded operands it is within bf16's unit roundoff
    (2^-8) in relative L2."""
    from repro.models.diffusion_nets import (joint_attention_flash,
                                             joint_attention_xla)

    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    img = jax.random.normal(ks[0], (b, n_img, width))
    txt = jax.random.normal(ks[1], (b, n_txt, width))
    k = jax.random.normal(ks[2], (b, n_img + n_txt, width))
    v = jax.random.normal(ks[3], (b, n_img + n_txt, width))
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    for q in (img, txt):
        out = joint_attention_flash(q, k, v, heads, block_q=block_q,
                                    block_k=block_k, interpret=True)
        assert out.shape == q.shape and out.dtype == q.dtype
        ref = joint_attention_xla(bf(q), bf(k), bf(v), heads)
        assert _rel_l2(out, ref) < 2.0 ** -8


@pytest.mark.parametrize("b,heads,n,block_q,block_k", [
    (2, 6, 64, 16, 32),
    (1, 38, 32, 32, 16),
], ids=["6-heads", "38-heads"])
def test_image_only_attention_flash_matches_einsum(b, heads, n, block_q,
                                                   block_k):
    """MMDiT-X's image-only attention: queries, keys and values all of the
    image tokens, heads of 64, the key count a multiple of ``block_k`` (no
    padded keys, no mask)."""
    from repro.models.diffusion_nets import (joint_attention_flash,
                                             joint_attention_xla)

    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q, k, v = (jax.random.normal(kk, (b, n, heads * 64)) for kk in ks)
    out = joint_attention_flash(q, k, v, heads, block_q=block_q,
                                block_k=block_k, interpret=True)
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    assert _rel_l2(out, joint_attention_xla(bf(q), bf(k), bf(v), heads)) \
        < 2.0 ** -8


@pytest.mark.parametrize("heads,head_dim,kv_heads,grid", [
    (38, 64, 38, (1, 19, 2, 3)),  # SD3.5 Large: 19 groups of 2 heads
    (24, 64, 24, (1, 12, 2, 3)),  # SD3.5 Medium
    (6, 32, 6, (1, 2, 3)),  # 6 heads of 32 do not fill whole groups of 4
    (2, 64, 2, (1, 2, 3)),  # one group: every head in one step, as before
    (8, 64, 4, (1, 2, 3)),  # GQA keeps the one-step body
    (4, 152, 4, (1, 2, 3)),  # heads wider than a lane tile
    (4, 128, 4, (1, 2, 3)),  # heads that fill a lane tile
    (8, 32, 8, (1, 2, 2, 3)),  # 2 groups of 4 heads of 32
])
def test_head_group_grid(monkeypatch, heads, head_dim, kv_heads, grid):
    """Heads narrower than a lane tile get a head-group grid axis, 128
    columns of q, k, v and out per step; every other shape keeps the
    (B, nQ, nK) grid and blocks of every head's columns."""
    from repro.kernels.flash_attention import kernel as km

    seen = []
    real = km.pl.pallas_call

    def spy(*a, **kw):
        seen.append((kw["grid"], kw["in_specs"][0].block_shape))
        return real(*a, **kw)

    monkeypatch.setattr(km.pl, "pallas_call", spy)
    q = jax.ShapeDtypeStruct((1, 32, heads * head_dim), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 48, kv_heads * head_dim), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: km.flash_attention_fwd(
        q, k, v, heads=heads, kv_heads=kv_heads, causal=False, block_q=16,
        block_k=16), q, kv, kv)
    cols = 128 if len(grid) == 4 else heads * head_dim
    assert seen == [(grid, (1, 16, cols))]


def test_joint_attention_is_the_einsum_path_on_cpu():
    """Off the TPU, the platform switch lowers the einsum path: outputs and
    gradients are those of ``joint_attention_xla`` to the bit."""
    from repro.models.diffusion_nets import joint_attention, joint_attention_xla

    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 16, 32))
    k = jax.random.normal(ks[1], (2, 20, 32))
    v = jax.random.normal(ks[2], (2, 20, 32))
    want = joint_attention_xla(q, k, v, 4)
    np.testing.assert_array_equal(joint_attention(q, k, v, 4), want)
    np.testing.assert_array_equal(
        jax.jit(joint_attention, static_argnums=3)(q, k, v, 4),
        jax.jit(joint_attention_xla, static_argnums=3)(q, k, v, 4))
    loss = lambda f: lambda q, k, v: (f(q, k, v, 4) ** 2).sum()  # noqa: E731
    got = jax.grad(loss(joint_attention), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(joint_attention_xla), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
