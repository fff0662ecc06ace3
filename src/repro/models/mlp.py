"""MLPs: gated dense (SwiGLU/GeGLU) and mixture-of-experts with sort-based
dispatch.  The MoE has two execution paths:

* local (mesh=None): single-device gather/scatter dispatch — used by smoke
  tests and small-scale training.
* sharded (mesh given): ``shard_map`` over the "model" axis — each shard owns
  ``E/tp`` experts, gathers its own tokens, computes, and ``psum``s the
  combined output.  This is the expert-parallel (EP=TP) production path; it
  avoids the O(T·E·C) one-hot dispatch tensor of the GShard formulation.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common as cm
from repro.configs.base import ArchConfig, MoEConfig

Array = jax.Array


# ---------------------------------------------------------------------------
# Dense gated MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    dt = cm.dtype_of(cfg)
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": cm.dense_init(k1, cfg.d_model, (f,), dt),
        "w_up": cm.dense_init(k2, cfg.d_model, (f,), dt),
        "w_down": cm.dense_init(k3, f, (cfg.d_model,), dt),
    }


def mlp_fwd(p: dict, cfg: ArchConfig, x: Array) -> Array:
    act = cm.act_fn(cfg.act)
    g = act(jnp.einsum("...d,df->...f", x, p["w_gate"]))
    u = jnp.einsum("...d,df->...f", x, p["w_up"])
    return jnp.einsum("...f,fd->...d", g * u, p["w_down"])


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ArchConfig) -> dict:
    m: MoEConfig = cfg.moe
    dt = cm.dtype_of(cfg)
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts

    def stack_init(k, shape, fan_in):
        w = jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
        return (w / jnp.sqrt(fan_in)).astype(dt)

    p = {
        "router": cm.dense_init(ks[0], d, (e,), jnp.float32),
        "we_gate": stack_init(ks[1], (e, d, f), d),
        "we_up": stack_init(ks[2], (e, d, f), d),
        "we_down": stack_init(ks[3], (e, f, d), f),
    }
    if m.n_shared:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=f * m.n_shared)
    return p


def _capacity(n_tokens: int, m: MoEConfig) -> int:
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts) + 1
    return max(8, min(c, n_tokens))


def _dispatch_compute(xf, we_gate, we_up, we_down, top_idx, top_p, capacity, act,
                      expert_lo=0):
    """Sort-based MoE dispatch for a block of experts.

    xf: (T, D) tokens; we_*: (E_blk, ...) local expert weights;
    top_idx/top_p: (T, k) global expert assignment; expert_lo: first global
    expert id owned by this block.  Returns (T, D) combined output.
    """
    t, d = xf.shape
    e_blk = we_gate.shape[0]
    k = top_idx.shape[1]
    flat_e = top_idx.reshape(-1) - expert_lo  # (T*k,) local expert ids
    flat_tok = jnp.repeat(jnp.arange(t), k)
    flat_w = top_p.reshape(-1)

    valid = (flat_e >= 0) & (flat_e < e_blk)
    sort_key = jnp.where(valid, flat_e, e_blk)  # invalid sorts to the end
    order = jnp.argsort(sort_key, stable=True)
    se, st, sw = sort_key[order], flat_tok[order], flat_w[order]
    sv = valid[order]

    # position within expert: arange - start offset of that expert
    counts = jnp.bincount(se, length=e_blk + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k) - starts[se]
    keep = sv & (pos < capacity)

    buf_rows = e_blk * capacity
    slot = jnp.where(keep, se * capacity + pos, buf_rows)  # overflow → dropped row
    buf = jnp.zeros((buf_rows + 1, d), xf.dtype).at[slot].set(xf[st])
    buf = buf[:buf_rows].reshape(e_blk, capacity, d)

    h = act(jnp.einsum("ecd,edf->ecf", buf, we_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, we_up
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, we_down).reshape(buf_rows, d)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, d), out_buf.dtype)], axis=0)

    contrib = out_buf[slot] * jnp.where(keep, sw, 0.0)[:, None].astype(out_buf.dtype)
    return jnp.zeros((t, d), xf.dtype).at[st].add(contrib)


def _route(router, xf, m: MoEConfig):
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.clip(jnp.sum(top_p, -1, keepdims=True), 1e-9)  # renorm
    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_idx, m.n_experts), axis=1), axis=0)
    aux = m.n_experts * jnp.sum(me * ce)
    return top_p, top_idx, aux


def moe_fwd(
    p: dict,
    cfg: ArchConfig,
    x: Array,
    *,
    mesh=None,
    axis: str = "model",
):
    """Returns (out, aux_loss). x: (B, S, D).

    Sharded path: routing, dispatch, expert GEMMs, *and the shared expert*
    all live inside one shard_map — routing is recomputed per model shard
    (redundant 0.8% FLOPs) instead of letting SPMD all-gather the (T, E)
    router probabilities per layer, and the shared expert joins the single
    bf16 psum instead of a separate f32 partial-sum all-reduce (found via
    the §Perf collective breakdown — see EXPERIMENTS.md)."""
    m: MoEConfig = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    act = cm.act_fn(cfg.act)

    if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        top_p, top_idx, aux = _route(p["router"], xf, m)
        capacity = _capacity(b * s, m)
        out = _dispatch_compute(
            xf, p["we_gate"], p["we_up"], p["we_down"], top_idx, top_p, capacity, act
        )
        if m.n_shared:
            out = out + mlp_fwd(p["shared"], cfg, xf)
        return out.reshape(b, s, d), aux

    tp = mesh.shape[axis]
    e_blk = m.n_experts // tp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    # capacity is per *data-shard* token block — the shard_map body only
    # ever sees b·s / n_batch_shards tokens (sizing it from the global
    # count inflates every expert buffer by the data-parallel degree)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    if (b * s) % n_batch_shards == 0:
        local_tokens = b * s // n_batch_shards
    else:
        local_tokens = b * s  # unsharded token block (e.g. batch=1)
    capacity = _capacity(local_tokens, m)
    has_shared = bool(m.n_shared)

    def shard_fn(xf_l, router, wg, wu, wd, shared):
        idx = jax.lax.axis_index(axis)
        top_p, top_idx, aux = _route(router, xf_l, m)
        out_l = _dispatch_compute(
            xf_l, wg, wu, wd, top_idx, top_p, capacity, act, expert_lo=idx * e_blk
        )
        if has_shared:
            # local F-chunk of the shared expert; joins the same bf16 psum
            g = act(jnp.einsum("td,df->tf", xf_l, shared["w_gate"]))
            u = jnp.einsum("td,df->tf", xf_l, shared["w_up"])
            out_l = out_l + jnp.einsum("tf,fd->td", (g * u).astype(xf_l.dtype),
                                       shared["w_down"]).astype(out_l.dtype)
        out_l = jax.lax.psum(out_l, axis)
        # routing is recomputed identically on every model shard (invarying
        # over `axis`), so aux only needs averaging over the batch axes
        aux = jax.lax.pmean(aux, batch_axes) if batch_axes else aux
        return out_l, aux

    shared_p = p.get("shared", {"w_gate": jnp.zeros((d, tp)), "w_up": jnp.zeros((d, tp)),
                                "w_down": jnp.zeros((tp, d))})
    shared_specs = {"w_gate": P(None, axis), "w_up": P(None, axis),
                    "w_down": P(axis, None)}
    # jitted: an eager shard_map over an Explicit-axis mesh (the
    # default of jax.make_mesh) fails at the first gather in its body
    out, aux = jax.jit(jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None),
            P(None, None),
            P(axis, None, None),
            P(axis, None, None),
            P(axis, None, None),
            shared_specs,
        ),
        out_specs=(P(batch_axes, None), P()),
    ))(xf, p["router"], p["we_gate"], p["we_up"], p["we_down"], shared_p)
    return out.reshape(b, s, d), aux
