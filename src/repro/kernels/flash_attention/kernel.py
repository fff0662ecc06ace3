"""Pallas TPU flash attention (forward): online-softmax over KV blocks.

TPU-native design decisions (vs a CUDA port):
* sequence-major layout: q is (B, S, H·D), k and v are (B, T, KV·D), as a
  model's projections write them, so no head transpose reaches HBM.  Every
  head of a block is handled in the kernel body (static loop, column slices
  in VMEM); head count comes from the caller, head dim from the shapes.
* grid = (B, nQ, nK) with the KV dimension **minor-most** — TPU grids are
  sequential in the last dimension, so the (m, l, acc) running state lives in
  VMEM scratch across the KV steps of one q-block.  The two leading grid
  axes are ``parallel``, the KV axis ``arbitrary``.
* the MXU is fed in the inputs' own dtype with f32 accumulation: bf16 inputs
  run one bf16 pass for QKᵀ and for PV (probabilities cast to v's dtype), f32
  inputs stay f32.  Scores, running max and sum stay f32.
* m and l are kept per head as 2-D ``(block_q, 128)`` scratch (every lane
  holds the row's value): a row reduction lands there without a relayout.
* GQA: head h reads the k/v columns of head h // group — no KV replication
  in HBM.
* causal + sliding-window masking via block-level iota comparison; with
  neither, only the last KV block is masked, and only when the caller padded
  the keys.  Logit softcap folded into the same VPU epilogue as the 1/√d
  scale.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # lane width of the m and l scratch
MIB = 2 ** 20


def vmem_limit(block_q, block_k, width, kv_width, heads, itemsize) -> int:
    """Scoped VMEM the kernel asks for: its double-buffered q, k, v and out
    blocks, its f32 scratch and every head's f32 scores and probabilities,
    at least 40 MiB and at most 100 of the 128 MiB a v5e core has.  The rest
    is XLA's, for the program's other buffers: on the MMDiT path a 100 MiB
    request made it move the medium role's residual stream into VMEM and
    slowed that step by 40% on a v5e, while 40 MiB covers 512-row blocks at
    width 2432."""
    blocks = 2 * itemsize * (2 * block_q * width + 2 * block_k * kv_width)
    scratch = 4 * (block_q * width + 2 * heads * block_q * LANES)
    scores = 4 * 2 * heads * block_q * block_k
    return min(100 * MIB, max(40 * MIB, blocks + scratch + scores))


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, heads: int, group: int, head_dim: int, scale: float, causal: bool,
    window: Optional[int], softcap: Optional[float], block_q: int,
    block_k: int, n_k: int, kv_len: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    def cols(h):
        return slice(h * head_dim, (h + 1) * head_dim)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def mask_of(pad):
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if pad:
            mask &= k_pos < kv_len  # padded keys never attend
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        return mask

    def step(mask):
        for h in range(heads):
            q = q_ref[0, :, cols(h)]  # (BQ, D)
            k = k_ref[0, :, cols(h // group)]  # (BK, D)
            v = v_ref[0, :, cols(h // group)]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (BQ, BK)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            if mask is not None:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h]  # (BQ, LANES)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            if mask is not None:
                p = jnp.where(mask, p, 0.0)  # rows with no valid keys: zero
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[:, cols(h)] = (
                acc_scr[:, cols(h)] * alpha[:, :1]
                + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            m_scr[h] = m_new

    padded = kv_len < n_k * block_k
    if causal or window is not None:
        step(mask_of(padded))
    elif padded:  # only the last KV block holds padded keys
        @pl.when(ki < n_k - 1)
        def _body():
            step(None)

        @pl.when(ki == n_k - 1)
        def _last():
            step(mask_of(True))
    else:
        step(None)

    @pl.when(ki == n_k - 1)
    def _finish():
        for h in range(heads):
            l = l_scr[h][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows (window) → 0
            o_ref[0, :, cols(h)] = (acc_scr[:, cols(h)] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,  # (B, S, H·D)
    k: jnp.ndarray,  # (B, T, KV·D)
    v: jnp.ndarray,
    *,
    heads: int,
    kv_heads: Optional[int] = None,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    kv_len: Optional[int] = None,
) -> jnp.ndarray:
    b, s, width = q.shape
    t, kv_width = k.shape[1], k.shape[2]
    kv_heads = heads if kv_heads is None else kv_heads
    d = width // heads
    assert width == heads * d and kv_width == kv_heads * d
    kv_len = t if kv_len is None else kv_len
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, "caller pads (ops.py)"
    n_q, n_k = s // block_q, t // block_k

    kernel = functools.partial(
        _attn_kernel, heads=heads, group=heads // kv_heads, head_dim=d,
        scale=1.0 / (d ** 0.5), causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, n_k=n_k,
        kv_len=kv_len,
    )
    itemsize = jnp.dtype(q.dtype).itemsize
    cost = pl.CostEstimate(
        flops=4 * b * heads * s * kv_len * d,
        transcendentals=b * heads * s * kv_len,
        bytes_accessed=itemsize * b * (2 * s * width + 2 * t * kv_width * n_q),
    )
    return pl.pallas_call(
        kernel,
        grid=(b, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, width), lambda i, qi, ki: (i, qi, 0)),
            pl.BlockSpec((1, block_k, kv_width), lambda i, qi, ki: (i, ki, 0)),
            pl.BlockSpec((1, block_k, kv_width), lambda i, qi, ki: (i, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, width),
                               lambda i, qi, ki: (i, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, width), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((heads, block_q, LANES), jnp.float32),
            pltpu.VMEM((heads, block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, width), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(block_q, block_k, width, kv_width,
                                        heads, itemsize)),
        cost_estimate=cost,
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
