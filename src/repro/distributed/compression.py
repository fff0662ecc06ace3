"""Compressed cross-pod collectives (DiLoCo-style periodic sync with error
feedback in launch/train.py).

The quantizers themselves live in :mod:`repro.quantization` — one module owns
every int8 round-trip (relay handoff transport, optimizer state, and these
collectives) so the relay's Eq.1-style deviation model and the collective's
error feedback share one code path.  This module keeps the collective
(`compressed_psum`); the historical quantizer re-exports completed their
deprecation cycle (DeprecationWarning through the previous releases) and now
raise ImportError pointing at the new home.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.quantization import fused_error_feedback_step, get_quantizer

Array = jax.Array

# historical API, now in repro.quantization — the lazy warning re-export
# shipped for the deprecation window; the window is over, so resolving an
# old name is now a hard error that says exactly where to import from
_MOVED = (
    "quant_rowwise", "dequant_rowwise", "quant_error",
    "quant_log8", "dequant_log8", "LOG8_RANGE",
    "latent_roundtrip_int8", "latent_roundtrip",
)


def __getattr__(name: str):
    if name in _MOVED:
        raise ImportError(
            f"repro.distributed.compression.{name} was removed after its "
            f"deprecation cycle; import repro.quantization.{name} instead"
        )
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def compressed_psum(tree, mesh, axis: str = "pod", error_state=None,
                    quantizer="rowwise"):
    """Mean-reduce a pytree across ``axis`` in int8 with error feedback.

    Each shard quantizes (value + carried error) with ``quantizer`` (any
    name registered in ``repro.quantization.QUANTIZERS``), the dequantized
    payloads are psum'd, and the residual is carried to the next sync — so
    the accumulated mean converges to exact even though each individual
    sync is lossy.  Returns (reduced_tree, new_error_state).

    The per-shard round-trip goes through the *fused* quantizer step
    (``repro.quantization.fused_error_feedback_step`` — the same path the
    fused relay boundaries compose): the reconstruction computed for the
    error carry is the one fed to the psum, so the payload dequantizes
    exactly once per shard instead of twice.  Bit-identical to the
    two-dequant form.
    """
    qz = get_quantizer(quantizer)
    n = mesh.shape[axis]
    if error_state is None:
        error_state = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tree)

    def one(x, err):
        def body(x_l, e_l):
            _, rec, new_err = fused_error_feedback_step(x_l, e_l, qz)
            tot = jax.lax.psum(rec, axis)
            return tot / n, new_err

        spec = P(*([None] * x.ndim))
        # jitted: an eager shard_map over an Explicit-axis mesh (the
        # default of jax.make_mesh) fails at the first gather in its body
        return jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec, spec), out_specs=(spec, spec),
        ))(x, err)

    flat, treedef = jax.tree.flatten(tree)
    eflat = jax.tree.leaves(error_state)
    out, errs = zip(*[one(x, e) for x, e in zip(flat, eflat)])
    return jax.tree.unflatten(treedef, out), jax.tree.unflatten(treedef, errs)
