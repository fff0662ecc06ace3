"""Samplers: VP-DDIM (paper Eq. 2) and rectified-flow Euler (paper Eq. 3),
with classifier-free guidance and opt-in trajectory capture (for the Fig. 2
latent-intensity analysis).

Two loop backends share the same per-step math:

* ``capture_traj=True`` — ``jax.lax.scan`` accumulating the full
  ``(steps, batch, *latent)`` trajectory stack.  Needs concrete
  ``start``/``stop`` (the scan length is static).  Analysis-path only.
* ``capture_traj=False`` — ``jax.lax.fori_loop`` carrying just the latent.
  ``start``/``stop`` may be *traced* integers, which is what lets the
  executor's shape-keyed compile cache serve every relay step of a family
  from one compiled program.  The hot serving path always runs this way —
  no O(steps) trajectory buffer is ever materialized.

Both backends produce bit-identical latents (locked by
tests/test_program_ir.py): the step bodies are the same function and XLA
preserves float semantics across scan/fori lowering.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.schedules import vp_alpha_bar

# denoiser signature: eps/v = fn(params, x, sigma_or_t, cond)


def cfg_combine(fn, params, x, t, cond, uncond, scale: float):
    if uncond is None or scale == 1.0:
        return fn(params, x, t, cond)
    e_c = fn(params, x, t, cond)
    e_u = fn(params, x, t, uncond)
    return e_u + scale * (e_c - e_u)


@jax.named_scope("sampler_update")
def ddim_update(x, eps, ab_t, ab_s):
    """The DDIM update's elementwise tail (Eq. 2, VP parameterization):
    given the guided ε̂ and the (ᾱ_t, ᾱ_s) pair, produce the next latent.
    Kept in the *two-term* form (x̂0 then recombine) — the algebraically
    collapsed affine form is not bit-identical, and the fused boundary
    kernels (:mod:`repro.kernels.fused_sampler`) must match this exactly.
    Named ``sampler_update`` in the compiled program's metadata, as is
    :func:`rf_update`."""
    x0_hat = (x - jnp.sqrt(1 - ab_t) * eps) / jnp.sqrt(ab_t)
    return jnp.sqrt(ab_s) * x0_hat + jnp.sqrt(1 - ab_s) * eps


@jax.named_scope("sampler_update")
def rf_update(x, v, dt):
    """The rectified-flow Euler update's elementwise tail (Eq. 3)."""
    return x + dt * v


def step_coeffs(kind: str, sigmas, i):
    """The (2,) coefficient vector of the step-update tail at ladder entry
    ``i`` — the traced operand the fused boundary kernels take: "ddim" →
    (ᾱ_t, ᾱ_s); "rf" → (Δt, 0).  ``i`` may be a traced int32."""
    if kind == "ddim":
        return jnp.stack([vp_alpha_bar(sigmas[i]), vp_alpha_bar(sigmas[i + 1])])
    dt = sigmas[i + 1] - sigmas[i]
    return jnp.stack([dt, jnp.zeros_like(dt)])


def step_update(kind: str, x, eps, coeffs):
    """Apply one sampler-step tail from its :func:`step_coeffs` vector —
    the shared math of :func:`ddim_step` / :func:`rf_euler_step` and the
    fused int8 boundary (bit-identical by construction)."""
    if kind == "ddim":
        return ddim_update(x, eps, coeffs[0], coeffs[1])
    return rf_update(x, eps, coeffs[0])


def ddim_step(eps_fn, params, x, sigmas, i, cond, uncond, guidance):
    """One DDIM update (Eq. 2, VP parameterization) from ladder entry i."""
    sig_t = sigmas[i]
    sig_s = sigmas[i + 1]
    ab_t = vp_alpha_bar(sig_t)
    ab_s = vp_alpha_bar(sig_s)
    eps = cfg_combine(eps_fn, params, x, sig_t, cond, uncond, guidance)
    return ddim_update(x, eps, ab_t, ab_s)


def rf_euler_step(v_fn, params, x, times, i, cond, uncond, guidance):
    """One rectified-flow Euler update (Eq. 3): x + Δt·v(x, t)."""
    t = times[i]
    dt = times[i + 1] - times[i]
    v = cfg_combine(v_fn, params, x, t, cond, uncond, guidance)
    return rf_update(x, v, dt)


def _sample(
    step: Callable,
    fn: Callable,
    params,
    x: jnp.ndarray,
    sigmas: jnp.ndarray,
    cond: jnp.ndarray,
    start,
    stop,
    uncond,
    guidance: float,
    capture_traj: bool,
):
    stop = len(sigmas) - 1 if stop is None else stop
    if not capture_traj:
        x_final = jax.lax.fori_loop(
            start, stop,
            lambda i, x: step(fn, params, x, sigmas, i, cond, uncond, guidance),
            x,
        )
        return x_final, None
    idx = jnp.arange(start, stop)  # needs concrete bounds

    def body(x, i):
        x_next = step(fn, params, x, sigmas, i, cond, uncond, guidance)
        return x_next, x_next

    return jax.lax.scan(body, x, idx)


def ddim_sample(
    eps_fn: Callable,
    params,
    x: jnp.ndarray,
    sigmas: jnp.ndarray,
    cond: jnp.ndarray,
    *,
    start: int = 0,
    stop: Optional[int] = None,
    uncond: Optional[jnp.ndarray] = None,
    guidance: float = 1.0,
    capture_traj: bool = True,
):
    """DDIM (Eq. 2) in VP parameterization over sigma ladder entries
    [start, stop).  x is the latent at noise level sigmas[start] in VP coords.
    Returns (x_final, trajectory) — trajectory of shape (steps, *x.shape),
    or ``None`` with ``capture_traj=False`` (the hot path: no O(steps)
    stack, and start/stop may be traced)."""
    return _sample(ddim_step, eps_fn, params, x, sigmas, cond, start, stop,
                   uncond, guidance, capture_traj)


def rf_euler_sample(
    v_fn: Callable,
    params,
    x: jnp.ndarray,
    times: jnp.ndarray,
    cond: jnp.ndarray,
    *,
    start: int = 0,
    stop: Optional[int] = None,
    uncond: Optional[jnp.ndarray] = None,
    guidance: float = 1.0,
    capture_traj: bool = True,
):
    """Rectified-flow Euler integration (Eq. 3): x_{i+1} = x_i + Δt·v(x_i,t_i).
    Same capture/trajectory contract as :func:`ddim_sample`."""
    return _sample(rf_euler_step, v_fn, params, x, times, cond, start, stop,
                   uncond, guidance, capture_traj)


def sampler_for(kind: str) -> Callable:
    """The family's sampler: "ddim" → :func:`ddim_sample`, "rf" →
    :func:`rf_euler_sample`."""
    return ddim_sample if kind == "ddim" else rf_euler_sample


def vp_noise(key, x0: jnp.ndarray, sigma) -> jnp.ndarray:
    """Forward-noise a clean latent to level σ in VP coords."""
    ab = vp_alpha_bar(sigma)
    n = jax.random.normal(key, x0.shape, x0.dtype)
    return jnp.sqrt(ab) * x0 + jnp.sqrt(1 - ab) * n


def rf_noise(key, x0: jnp.ndarray, t) -> jnp.ndarray:
    n = jax.random.normal(key, x0.shape, x0.dtype)
    return (1.0 - t) * x0 + t * n
