"""Pallas TPU fused classifier-free-guidance + sampler-step kernel.

The per-step elementwise tail of diffusion serving reads the latent and two
denoiser outputs and writes the next latent.  Unfused, XLA materializes the
guided ε̂ and the x̂0 estimate — 5 HBM round-trips over the latent; fused,
it is one read of (x, ε_c, ε_u) and one write:  a 2.5× cut of the sampler
tail's HBM traffic (the denoiser itself still dominates, but at Vega-class
sizes the tail is ~8% of step time on TPU — see EXPERIMENTS.md §Perf).

The DDIM update is algebraically collapsed to x' = c1·x + c2·ε̂ (affine), so
one kernel serves both families: mode "ddim" (c1,c2) and mode "rf" (dt).

**Fused int8 boundary kernels** (`fused_cfg_step_quant_fwd` /
`fused_cfg_step_dequant_fwd`): the segment-boundary steps of a compressed
relay handoff.  The emit kernel runs the *last* edge-segment step and writes
the wire payload — (q int8, one fp32 scale per row) over the handoff's
channel-row layout — without materializing the fp16 latent it would
otherwise round-trip through HBM; the consume kernel reads (q, s) in-kernel
and runs the *first* device-segment step straight off the wire format.
Unlike the affine kernel above, these keep the DDIM update in the two-term
form of ``repro.core.samplers.ddim_update`` with the (ᾱ_t, ᾱ_s) pair as a
traced (1, 2) operand: the affine collapse is *not* bit-identical (≈5e-7),
and the emitted int8 scales must match `repro.quantization.latent_roundtrip`
to the bit (the relay's Eq. 1 deviation accounting is exact-compared in the
golden suites).  Guidance is a static specialization: ``guidance == 1.0``
uses ε_c directly, mirroring ``cfg_combine``'s skip path."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_kernel(x_ref, ec_ref, eu_ref, o_ref, *, guidance, c1, c2, mode):
    x = x_ref[...].astype(jnp.float32)
    ec = ec_ref[...].astype(jnp.float32)
    eu = eu_ref[...].astype(jnp.float32)
    eps = eu + guidance * (ec - eu)
    if mode == "ddim":
        out = c1 * x + c2 * eps
    else:  # rf euler
        out = x + c1 * eps
    o_ref[...] = out.astype(o_ref.dtype)


def fused_cfg_step_fwd(
    x: jnp.ndarray,  # (N, C) flattened latent
    eps_c: jnp.ndarray,
    eps_u: jnp.ndarray,
    *,
    guidance: float,
    c1: float,
    c2: float,
    mode: str,
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    n, c = x.shape
    block_n = min(block_n, n)
    assert n % block_n == 0
    kernel = functools.partial(
        _fused_kernel, guidance=guidance, c1=c1, c2=c2, mode=mode
    )
    spec = pl.BlockSpec((block_n, c), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, c), x.dtype),
        interpret=interpret,
    )(x, eps_c, eps_u)


# ---------------------------------------------------------------------------
# fused int8 segment-boundary kernels (emit / consume the wire format)
# ---------------------------------------------------------------------------


def _combine_update(x, ec, eu, cf, *, guidance, mode):
    """Shared in-kernel tail: static-guidance CFG combine + two-term step
    update (bit-identical to ``samplers.cfg_combine`` + ``step_update``)."""
    if guidance == 1.0:
        eps = ec
    else:
        eps = eu + guidance * (ec - eu)
    c0 = cf[0, 0]
    c1 = cf[0, 1]
    if mode == "ddim":
        x0_hat = (x - jnp.sqrt(1 - c0) * eps) / jnp.sqrt(c0)
        return jnp.sqrt(c1) * x0_hat + jnp.sqrt(1 - c1) * eps
    return x + c0 * eps  # rf euler


def _fused_quant_kernel(x_ref, ec_ref, eu_ref, cf_ref, q_ref, s_ref, *,
                        guidance, mode):
    x = x_ref[...].astype(jnp.float32)
    ec = ec_ref[...].astype(jnp.float32)
    eu = eu_ref[...].astype(jnp.float32)
    out = _combine_update(x, ec, eu, cf_ref[...], guidance=guidance, mode=mode)
    # row-wise symmetric int8 emit — quant_rowwise semantics, including the
    # amax == 0 guard (padded/all-zero rows get scale 1.0 and q ≡ 0)
    amax = jnp.max(jnp.abs(out), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(out / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def _fused_dequant_kernel(q_ref, s_ref, ec_ref, eu_ref, cf_ref, o_ref, *,
                          guidance, mode):
    x = q_ref[...].astype(jnp.float32) * s_ref[...]
    ec = ec_ref[...].astype(jnp.float32)
    eu = eu_ref[...].astype(jnp.float32)
    out = _combine_update(x, ec, eu, cf_ref[...], guidance=guidance, mode=mode)
    o_ref[...] = out.astype(o_ref.dtype)


# VMEM a boundary kernel's double-buffered blocks may take: half of the
# 16 MiB of scoped VMEM Mosaic grants a kernel on TPU v5e by default, the
# rest left to the float32 temporaries of the in-kernel step.
_VMEM_BLOCK_BUDGET = 8 * 2**20


def _boundary_grid(r: int, row_bytes: int, block_r: int | None = None):
    """(rows per block, padding rows, grid steps) for R wire rows.

    A block holds whole rows, since the rowwise amax needs the full row.
    By default it takes as many rows as fit ``_VMEM_BLOCK_BUDGET`` with every
    operand double-buffered (``row_bytes`` = one row of all operands), in
    multiples of 8 sublanes and at most 256: at 8x8 latents that is 256
    rows, at 128x128 latents 16 rows."""
    if block_r is None:
        fit = _VMEM_BLOCK_BUDGET // (2 * row_bytes)
        block_r = max(8, min(256, fit) // 8 * 8)
    block_r = min(block_r, r)
    pad = (-r) % block_r
    return block_r, pad, (r + pad) // block_r


def fused_cfg_step_quant_fwd(
    x: jnp.ndarray,  # (R, C) wire rows: R = batch·channels, C = H·W
    eps_c: jnp.ndarray,
    eps_u: jnp.ndarray,
    coeffs: jnp.ndarray,  # (1, 2) fp32: (ᾱ_t, ᾱ_s) for ddim, (Δt, 0) for rf
    *,
    guidance: float,
    mode: str,
    block_r: int | None = None,
    interpret: bool = False,
):
    """Last edge-segment step, fused with the wire emit: one read of
    (x, ε_c, ε_u) and one write of (q int8, s fp32) per row — the fp16
    next-latent never touches HBM.  Returns ``(q, s)`` with ``s`` shaped
    (R, 1).  Rows pad to the block with zeros (guarded scale 1.0)."""
    r, c = x.shape
    row_bytes = c * (x.dtype.itemsize + eps_c.dtype.itemsize
                     + eps_u.dtype.itemsize + 1) + 4
    block_r, pad, steps = _boundary_grid(r, row_bytes, block_r)
    if pad:
        z = jnp.zeros((pad, c), x.dtype)
        x = jnp.concatenate([x, z])
        eps_c = jnp.concatenate([eps_c, z.astype(eps_c.dtype)])
        eps_u = jnp.concatenate([eps_u, z.astype(eps_u.dtype)])
    rp = r + pad
    kernel = functools.partial(_fused_quant_kernel, guidance=guidance,
                               mode=mode)
    spec = pl.BlockSpec((block_r, c), lambda i: (i, 0))
    q, s = pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[spec, spec, spec, pl.BlockSpec((1, 2), lambda i: (0, 0))],
        out_specs=[spec, pl.BlockSpec((block_r, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((rp, c), jnp.int8),
            jax.ShapeDtypeStruct((rp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, eps_c, eps_u, coeffs)
    return q[:r], s[:r]


def fused_cfg_step_dequant_fwd(
    q: jnp.ndarray,  # (R, C) int8 wire rows
    s: jnp.ndarray,  # (R, 1) fp32 scales
    eps_c: jnp.ndarray,
    eps_u: jnp.ndarray,
    coeffs: jnp.ndarray,  # (1, 2) fp32
    *,
    guidance: float,
    mode: str,
    block_r: int | None = None,
    interpret: bool = False,
):
    """First device-segment step, fused with the wire consume: the latent
    operand is read as (q int8, s fp32) and dequantized in-register — the
    step's HBM read of the latent shrinks to the int8 payload.  Output
    dtype follows ε_c.  Rows pad to the block with zeros."""
    r, c = q.shape
    row_bytes = c * (1 + 2 * eps_c.dtype.itemsize + eps_u.dtype.itemsize) + 4
    block_r, pad, steps = _boundary_grid(r, row_bytes, block_r)
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        s = jnp.pad(s, ((0, pad), (0, 0)))
        z = jnp.zeros((pad, c), eps_c.dtype)
        eps_c = jnp.concatenate([eps_c, z])
        eps_u = jnp.concatenate([eps_u, z])
    rp = r + pad
    kernel = functools.partial(_fused_dequant_kernel, guidance=guidance,
                               mode=mode)
    spec = pl.BlockSpec((block_r, c), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[
            spec,
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            spec,
            spec,
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rp, c), eps_c.dtype),
        interpret=interpret,
    )(q, s, eps_c, eps_u, coeffs)
    return out[:r]

