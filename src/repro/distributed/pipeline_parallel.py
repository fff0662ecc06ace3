"""GPipe-style pipeline parallelism over a "stage" mesh axis via shard_map +
collective_permute.

Each stage owns a contiguous slice of layers (stacked on a leading axis).
Microbatches stream through: at step t, stage p runs microbatch (t−p) and
passes activations to stage p+1 with ppermute.  After P−1 warm-up steps the
pipeline is full; total steps = n_micro + P − 1 (bubble fraction
(P−1)/(n_micro+P−1), reported by ``bubble_fraction``).

This module is the PP building block demonstrated on an MLP stack and
covered by equivalence tests (tests/test_distribution.py); the main archs
ship DP/TP/EP shardings (see DESIGN.md §6).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(
    layer_fn: Callable,  # (layer_params, x) → x, applied per layer
    stage_params,  # pytree; leaves (n_stages, layers_per_stage, ...)
    x,  # (n_micro, micro_batch, d) microbatched input
    mesh: Mesh,
    *,
    axis: str = "stage",
):
    """Returns f(x) with layers partitioned across the `axis` mesh dimension."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    steps = n_micro + n_stages - 1

    def stage_fn(params, xs):
        # params: (1, layers_per_stage, ...) local slice; xs: (n_micro, mb, d)
        sid = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params)

        def run_stage(h):
            def body(h, lp):
                return layer_fn(lp, h), None

            h, _ = jax.lax.scan(body, h, params)
            return h

        mb = xs.shape[1]
        d = xs.shape[2]
        # carries start as stage-varying so the scan carry types stay stable
        buf = jax.lax.pcast(jnp.zeros((mb, d), xs.dtype), (axis,), to="varying")
        out = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")

        def step(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (if in range); others use buf
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(sid == 0, 1, 0) * jnp.where(t < n_micro, 1, 0)
            h_in = jnp.where(inject, xs[mb_idx], buf)
            h_out = run_stage(h_in)
            # last stage emits microbatch (t − n_stages + 1)
            emit_idx = t - (n_stages - 1)
            do_emit = (sid == n_stages - 1) & (emit_idx >= 0)
            idx = jnp.clip(emit_idx, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(out, idx, 0, keepdims=False)
            new = jnp.where(do_emit, h_out, cur)
            out = jax.lax.dynamic_update_index_in_dim(out, new, idx, 0)
            # pass activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(h_out, axis, perm)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(step, (buf, out), jnp.arange(steps))
        # non-final stages hold zeros; psum broadcasts the final stage's out
        return jax.lax.psum(out, axis)

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    # jitted: an eager shard_map over an Explicit-axis mesh (the
    # default of jax.make_mesh) fails at the first gather in its body
    return jax.jit(jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(spec_p, P()), out_specs=P(),
    ))(stage_params, x)
