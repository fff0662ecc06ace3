"""Jit'd wrappers: flatten latents, pad, dispatch the fused kernels.

``fused_cfg_step`` wraps the affine CFG+step kernel over any latent shape;
``fused_cfg_step_quant`` / ``fused_cfg_step_dequant`` wrap the int8
boundary kernels over the handoff's wire-row layout (rows = per-channel
spatial slices, ``repro.quantization.latent_to_rows``) — row padding is
handled inside the fwd fns, so any row count works."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_sampler.kernel import (fused_cfg_step_dequant_fwd,
                                                fused_cfg_step_fwd,
                                                fused_cfg_step_quant_fwd)


@partial(
    jax.jit,
    static_argnames=("guidance", "c1", "c2", "mode", "block_n", "interpret"),
)
def fused_cfg_step(
    x: jnp.ndarray,  # any shape (latent batch)
    eps_c: jnp.ndarray,
    eps_u: jnp.ndarray,
    *,
    guidance: float = 1.0,
    c1: float = 1.0,
    c2: float = 0.0,
    mode: str = "ddim",
    block_n: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    shape = x.shape
    last = shape[-1]
    xf = x.reshape(-1, last)
    n = xf.shape[0]
    bn = min(block_n, n)
    pad = (-n) % bn
    if pad:
        z = jnp.zeros((pad, last), x.dtype)
        xf = jnp.concatenate([xf, z])
        eps_c = jnp.concatenate([eps_c.reshape(-1, last), z])
        eps_u = jnp.concatenate([eps_u.reshape(-1, last), z])
    else:
        eps_c = eps_c.reshape(-1, last)
        eps_u = eps_u.reshape(-1, last)
    out = fused_cfg_step_fwd(
        xf, eps_c, eps_u, guidance=guidance, c1=c1, c2=c2, mode=mode,
        block_n=bn, interpret=interpret,
    )
    return out[:n].reshape(shape)


@partial(jax.jit, static_argnames=("guidance", "mode", "block_r", "interpret"))
def fused_cfg_step_quant(
    x: jnp.ndarray,  # (..., C) wire rows (any leading dims)
    eps_c: jnp.ndarray,
    eps_u: jnp.ndarray,
    coeffs: jnp.ndarray,  # (2,) or (1, 2) fp32 step coefficients (traced)
    *,
    guidance: float = 1.0,
    mode: str = "ddim",
    block_r: int | None = None,
    interpret: bool = False,
):
    """Fused emit boundary over wire rows: the last segment step's output is
    written directly as ``(q int8, s fp32)`` — one scale per row, shaped
    like the input with the row length reduced to 1 for ``s``."""
    shape = x.shape
    last = shape[-1]
    q, s = fused_cfg_step_quant_fwd(
        x.reshape(-1, last), eps_c.reshape(-1, last), eps_u.reshape(-1, last),
        coeffs.astype(jnp.float32).reshape(1, 2),
        guidance=guidance, mode=mode, block_r=block_r, interpret=interpret,
    )
    return q.reshape(shape), s.reshape(shape[:-1] + (1,))


@partial(jax.jit, static_argnames=("guidance", "mode", "block_r", "interpret"))
def fused_cfg_step_dequant(
    q: jnp.ndarray,  # (..., C) int8 wire rows
    s: jnp.ndarray,  # (..., 1) fp32 scales
    eps_c: jnp.ndarray,
    eps_u: jnp.ndarray,
    coeffs: jnp.ndarray,  # (2,) or (1, 2) fp32 step coefficients (traced)
    *,
    guidance: float = 1.0,
    mode: str = "ddim",
    block_r: int | None = None,
    interpret: bool = False,
):
    """Fused consume boundary over wire rows: the first segment step reads
    the int8+scales payload in-kernel; returns the stepped rows in ε_c's
    dtype."""
    shape = q.shape
    last = shape[-1]
    out = fused_cfg_step_dequant_fwd(
        q.reshape(-1, last), s.reshape(-1, 1),
        eps_c.reshape(-1, last), eps_u.reshape(-1, last),
        coeffs.astype(jnp.float32).reshape(1, 2),
        guidance=guidance, mode=mode, block_r=block_r, interpret=interpret,
    )
    return out.reshape(shape)
