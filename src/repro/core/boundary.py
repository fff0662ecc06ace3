"""Fused int8 segment boundaries: the sampler step that *is* the handoff.

A compressed relay handoff used to be three separate dispatches bracketing
the samplers — quantize → wire → dequantize — so the fp16 latent was fully
materialized in HBM on both sides of every segment boundary.  This module
fuses the boundary into the steps themselves:

* **emit** — the *last* edge-segment step combines CFG, applies the
  two-term step update and writes the wire payload ``{"q" int8, "s" fp32}``
  over the handoff's channel-row layout
  (:func:`repro.quantization.quant_latent`) in one fused dispatch;
* **consume** — the *first* device-segment step reads the wire payload as
  its latent operand (the int8 rows dequantize in-register) and steps
  straight off it.

Two backends share one contract.  The default, on every backend the TPU
included, is a jnp composition under a single ``jax.jit`` — XLA fuses the
elementwise tail with the quantize (one latent read, one wire write).  The
hand-fused Pallas kernels (:mod:`repro.kernels.fused_sampler`) instantiate
the same math behind ``use_kernel=True``, which nothing on the served path
sets; they compile only for the TPU, run elsewhere in interpret mode only,
and are bit-parity-locked against the jnp path there
(``tests/test_fused_boundary.py``).

**Parity contract** (what ``tests/test_fused_boundary.py`` locks): against
the unfused `step → latent_roundtrip → step` sequence, `emit → consume`
produces the *exact* int8 payload and byte accounting, scales within 1
float32 ulp, and numerically equivalent latents/deviations (~1e-6
relative).  The tails reuse the same step math
(:func:`repro.core.samplers.step_update`, two-term form) and the same wire
halves (:func:`repro.quantization.quant_latent` / :func:`dequant_latent`)
the unfused path composes, but XLA repartitions the fused program — FMA
contraction and reciprocal-multiply selection differ per compilation
unit, so cross-unit bitwise identity is not a property CPU XLA offers.
The Pallas kernels, however, ARE bit-parity-locked against their jitted
jnp oracles in interpret mode — payload ints, scales and stepped rows all
exact.

The jitted tails live in a module-level cache keyed by static config
(kind, quantizer, guidance, flavor); :func:`warm` pre-fires them so the
first relay request doesn't eat their compile time, and
:func:`cache_stats` exposes per-config compiled-trace counts for the
telemetry asserts.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import samplers
from repro.quantization import (dequant_latent, latent_to_rows,
                                payload_bytes, quant_latent,
                                relative_deviation)

# emit flavors: what the fused producer step returns beyond the payload.
#   "wire"            — payload only (the serving fast path; kernel-backed
#                       on TPU: the fp16 latent never touches HBM)
#   "wire_dev"        — + the Eq. 1 deviation pct of the payload vs the
#                       stepped latent (relay/DAG accounting)
#   "wire_dev_latent" — + the stepped latent itself (graph nodes whose
#                       other consumers need it: joins, mixed edges, sink)
EMIT_FLAVORS = ("wire", "wire_dev", "wire_dev_latent")

_jits: Dict[Tuple, Callable] = {}  # static boundary config -> jitted tail


def _combine(ec, eu, guidance: float):
    """cfg_combine on pre-evaluated nets — same skip semantics (guidance
    1.0 returns ε_c untouched), so fused and unfused guidance follow the
    same code path."""
    if guidance == 1.0:
        return ec
    return eu + guidance * (ec - eu)


def _net_eps(fn, params, x, t, cond, uncond, guidance: float):
    """Evaluate the denoiser(s) for one step: (ε_c, ε_u, effective
    guidance).  Mirrors ``cfg_combine``'s call pattern: no uncond or unit
    scale → a single evaluation."""
    if uncond is None or guidance == 1.0:
        ec = fn(params, x, t, cond)
        return ec, ec, 1.0
    return fn(params, x, t, cond), fn(params, x, t, uncond), float(guidance)


def emit_fn(kind: str, quantizer: str = "rowwise", guidance: float = 1.0,
            flavor: str = "wire", use_kernel: bool = False,
            interpret: bool = False) -> Callable:
    """The cached jitted emit tail for one boundary config.

    Signature: ``tail(x, ec, eu, coeffs) -> dict`` with key ``"wire"`` (the
    payload) and, per ``flavor``, ``"dev_pct"`` / ``"latent"``.  ``coeffs``
    is the (2,) vector from :func:`samplers.step_coeffs`.  With
    ``use_kernel`` the Pallas emit kernel replaces the jnp tail
    (``flavor="wire"`` only — the accounting flavors keep the stepped
    latent live by definition, so there is nothing to elide)."""
    if flavor not in EMIT_FLAVORS:
        raise ValueError(f"unknown emit flavor {flavor!r}; one of {EMIT_FLAVORS}")
    if use_kernel and (flavor != "wire" or quantizer != "rowwise"):
        raise ValueError(
            "kernel-backed emit supports flavor='wire' with the rowwise "
            f"quantizer only (got flavor={flavor!r}, quantizer={quantizer!r})"
        )
    key = ("emit", kind, quantizer, float(guidance), flavor, use_kernel,
           interpret)
    if key in _jits:
        return _jits[key]

    if use_kernel:
        from repro.kernels.fused_sampler.ops import fused_cfg_step_quant

        def tail(x, ec, eu, coeffs):
            q, s = fused_cfg_step_quant(
                latent_to_rows(x), latent_to_rows(ec), latent_to_rows(eu),
                coeffs, guidance=float(guidance), mode=kind,
                interpret=interpret,
            )
            return {"wire": {"q": q, "s": s}}
    else:
        def tail(x, ec, eu, coeffs):
            out = samplers.step_update(kind, x, _combine(ec, eu, guidance),
                                       coeffs)
            qs, _ = quant_latent(out, quantizer)
            res = {"wire": qs}
            if flavor != "wire":
                rec = dequant_latent(qs, out.shape[-3:], out.dtype, quantizer)
                res["dev_pct"] = relative_deviation(out, rec) * 100.0
            if flavor == "wire_dev_latent":
                res["latent"] = out
            return res

    _jits[key] = jax.jit(tail)
    return _jits[key]


def peek_fn(quantizer: str = "rowwise") -> Callable:
    """The cached jitted wire→latent reconstruction,
    ``peek(q, s, latent_shape)`` — what the consuming step's denoiser reads
    (the same bits the unfused wire would deliver).  ``latent_shape`` is a
    static (H, W, C) tuple."""
    key = ("peek", quantizer)
    if key not in _jits:
        def f(q, s, latent_shape):
            return dequant_latent({"q": q, "s": s}, latent_shape,
                                  jnp.float32, quantizer)

        _jits[key] = jax.jit(f, static_argnames=("latent_shape",))
    return _jits[key]


def consume_fn(kind: str, quantizer: str = "rowwise", guidance: float = 1.0,
               use_kernel: bool = False, interpret: bool = False) -> Callable:
    """The cached jitted consume tail:
    ``tail(q, s, ec, eu, coeffs, latent_shape) -> next latent``.  The step
    update reads the wire payload directly (int8 rows instead of the fp32
    reconstruction); with ``use_kernel`` the Pallas consume kernel
    instantiates it (rowwise quantizer only)."""
    if use_kernel and quantizer != "rowwise":
        raise ValueError(
            "kernel-backed consume supports the rowwise quantizer only "
            f"(got {quantizer!r})"
        )
    key = ("consume", kind, quantizer, float(guidance), use_kernel, interpret)
    if key in _jits:
        return _jits[key]

    if use_kernel:
        from repro.kernels.fused_sampler.ops import fused_cfg_step_dequant
        from repro.quantization import rows_to_latent

        def tail(q, s, ec, eu, coeffs, latent_shape):
            rows = fused_cfg_step_dequant(
                q, s, latent_to_rows(ec), latent_to_rows(eu), coeffs,
                guidance=float(guidance), mode=kind, interpret=interpret,
            )
            return rows_to_latent(rows, latent_shape, jnp.float32)
    else:
        def tail(q, s, ec, eu, coeffs, latent_shape):
            x = dequant_latent({"q": q, "s": s}, latent_shape, jnp.float32,
                               quantizer)
            return samplers.step_update(kind, x, _combine(ec, eu, guidance),
                                        coeffs)

    _jits[key] = jax.jit(tail, static_argnames=("latent_shape",))
    return _jits[key]


# ---------------------------------------------------------------------------
# step-level drivers — what execute_program / the executor's segment fns call
# ---------------------------------------------------------------------------


def quant_step(kind: str, fn, params, x, sigmas, i, cond, uncond,
               guidance: float, *, quantizer: str = "rowwise",
               flavor: str = "wire", use_kernel: bool = False,
               interpret: bool = False) -> dict:
    """Run sampler step ``i`` and emit the wire payload in the same fused
    dispatch — the producer side of a compressed segment boundary.

    Returns a dict with ``"wire"`` (the ``{"q", "s"}`` payload),
    ``"bytes"`` (static payload bytes, same accounting as
    ``latent_roundtrip``), and per ``flavor`` ``"dev_pct"`` /
    ``"latent"``.  ``i`` may be a traced int32 (the executor's traced
    segment bounds).  The emit tail is named ``boundary_emit`` in the
    compiled program's metadata; the denoiser keeps its own names."""
    ec, eu, g = _net_eps(fn, params, x, sigmas[i], cond, uncond, guidance)
    with jax.named_scope("boundary_emit"):
        coeffs = samplers.step_coeffs(kind, sigmas, i)
        res = dict(emit_fn(kind, quantizer, g, flavor, use_kernel,
                           interpret)(x, ec, eu, coeffs))
    res["bytes"] = payload_bytes(res["wire"])
    return res


def dequant_step(kind: str, fn, params, qs: dict, latent_shape, sigmas, i,
                 cond, uncond, guidance: float, *,
                 quantizer: str = "rowwise", use_kernel: bool = False,
                 interpret: bool = False):
    """Run sampler step ``i`` straight off the wire payload — the consumer
    side of a compressed segment boundary.  The denoiser sees the
    reconstructed latent (the same payload the unfused wire delivers);
    the step tail reads the int8 payload.  Returns the next latent.  The
    reconstruction and the consume tail are named ``boundary_consume`` in
    the compiled program's metadata; the denoiser keeps its own names."""
    latent_shape = tuple(latent_shape)
    with jax.named_scope("boundary_consume"):
        x = peek_fn(quantizer)(qs["q"], qs["s"], latent_shape)
    ec, eu, g = _net_eps(fn, params, x, sigmas[i], cond, uncond, guidance)
    with jax.named_scope("boundary_consume"):
        coeffs = samplers.step_coeffs(kind, sigmas, i)
        return consume_fn(kind, quantizer, g, use_kernel, interpret)(
            qs["q"], qs["s"], ec, eu, coeffs, latent_shape
        )


# ---------------------------------------------------------------------------
# warm-up + telemetry
# ---------------------------------------------------------------------------


def warm(latent_shape, batch: int = 4, kinds=("ddim", "rf"),
         quantizer: str = "rowwise", guidance: float = 1.0) -> int:
    """Pre-compile the fused boundary tails for one latent shape: both
    sampler kinds, both emit accounting flavors, the wire peek and the
    consume tail.  Called from ``HandoffTransport.warm`` / the executor's
    JIT pre-fire so the first compressed relay request doesn't pay the
    boundary compiles.  Returns the number of tail calls fired (every one
    lands in :func:`cache_stats`)."""
    latent_shape = tuple(latent_shape)
    x = jnp.zeros((batch,) + latent_shape, jnp.float32)
    eps = jnp.zeros_like(x)
    n = 0
    for kind in kinds:
        # any valid coefficient pair compiles the trace; values don't matter
        coeffs = jnp.asarray([0.5, 0.6], jnp.float32)
        wire = None
        for flavor in ("wire", "wire_dev"):
            res = emit_fn(kind, quantizer, guidance, flavor)(x, eps, eps,
                                                             coeffs)
            wire = res["wire"]
            n += 1
        peek_fn(quantizer)(wire["q"], wire["s"], latent_shape)
        n += 1
        consume_fn(kind, quantizer, guidance)(
            wire["q"], wire["s"], eps, eps, coeffs, latent_shape
        )
        n += 1
    return n


def cache_stats() -> Dict[str, int]:
    """Compile-cache telemetry: per-config compiled-trace counts of every
    cached boundary tail (``jax.jit``'s own trace cache — one entry per
    shape signature seen).  The warm-path tests assert these are nonzero
    after :func:`warm` and *unchanged* after the first real request."""
    out = {}
    for key, fn in _jits.items():
        label = "/".join(str(k) for k in key)
        try:
            out[label] = int(fn._cache_size())
        except AttributeError:  # pragma: no cover - older jax
            out[label] = -1
    return out


def clear_cache() -> None:
    """Drop every cached boundary tail (test isolation)."""
    _jits.clear()
