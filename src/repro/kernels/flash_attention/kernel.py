"""Pallas TPU flash attention (forward): online-softmax over KV blocks.

TPU-native design decisions (vs a CUDA port):
* sequence-major layout: q is (B, S, H·D), k and v are (B, T, KV·D), as a
  model's projections write them, so no head transpose reaches HBM.  Every
  head of a block is handled in the kernel body (static loop, column slices
  in VMEM); head count comes from the caller, head dim from the shapes.
* grid = (B, nQ, nK) with the KV dimension **minor-most** — TPU grids are
  sequential in the last dimension, so the (m, l, acc) running state lives in
  VMEM scratch across the KV steps of one q-block.  The leading grid axes
  are ``parallel``, the KV axis ``arbitrary``.
* head groups: where at least two heads share a lane tile (D divides 64),
  there is no GQA, no mask but the padded keys' and no softcap, and there are
  more heads than fit one tile, the grid is (B, H·D/128, nQ, nK) and each
  step holds the 128 columns of G = 128/D heads: the body unrolls those few
  heads, not all of them, so at, say, 38 heads of 64 the Mosaic compile
  takes seconds and the VMEM asked for grows with the blocks, not the heads.
  Its body spends fewer vector operations per score, because a narrow head
  leaves the MXU idle lanes to use: a power-of-two scale such as 1/8 is
  applied to q before QKᵀ, where it is exact (any other scales the f32
  scores), PV multiplies by v with the other heads' lanes set to 1, so the
  same pass that accumulates a head's output sums its probabilities in the
  remaining lanes, and only the lane tiles that hold padded keys are masked.
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
import math
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


def cost_estimate(b, s, t, kv_len, width, kv_width, heads, block_q,
                  itemsize) -> pl.CostEstimate:
    """Operations and HBM bytes of one call on padded shapes: every row of
    the ``s`` padded queries over the ``kv_len`` real keys, q and out read
    and written once, k and v read once per query block."""
    return pl.CostEstimate(
        flops=4 * b * s * kv_len * width,
        transcendentals=b * heads * s * kv_len,
        bytes_accessed=itemsize * b * (2 * s * width
                                       + 2 * t * kv_width * (s // block_q)),
    )


def head_group(heads: int, kv_heads: int, head_dim: int, causal: bool,
               window: Optional[int], softcap: Optional[float]) -> int:
    """Heads per grid step of the head-group grid, or 0 where every head
    stays in one step (see the module docstring)."""
    g = LANES // head_dim if LANES % head_dim == 0 else 0
    if g < 2:  # a head that fills its lane tile leaves no lane for the sums
        return 0
    plain = not causal and window is None and softcap is None
    if plain and kv_heads == heads and heads % g == 0 and heads > g:
        return g
    return 0


def _grouped_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                    heads: int, head_dim: int, scale: float, block_k: int,
                    n_k: int, kv_len: int):
    """One step of the head-group grid: ``heads`` heads of ``head_dim`` in
    the 128 lanes of q, k, v and out.  m and l are kept per head, l in the
    lanes of the other heads (where the PV pass sums the probabilities),
    the output accumulator for all heads in their own lanes."""
    ki = pl.program_id(3)
    d = head_dim

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // d
    # a power-of-two scale (heads of 64, 16, 4) is exact on q in any dtype;
    # any other scales the f32 scores
    exact = math.frexp(scale)[0] == 0.5
    q = q_ref[0]
    if exact:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    k = k_ref[0]
    v = v_ref[0]
    # padded keys lie in the last block's last ``tail`` lanes only
    pad = n_k * block_k - kv_len
    tail = min(block_k, -(-pad // LANES) * LANES)

    def step(masked: bool):
        acc = acc_scr[...]
        for h in range(heads):
            s = jax.lax.dot_general(
                q[:, h * d:(h + 1) * d], k[:, h * d:(h + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            if not exact:
                s = s * scale
            v_sum = jnp.where(lane_head == h, v, jnp.ones_like(v))
            if masked:
                cut = block_k - tail
                pos = ki * block_k + cut + jax.lax.broadcasted_iota(
                    jnp.int32, (1, tail), 1)
                last = jnp.where(pos < kv_len, s[:, cut:], NEG_INF)
                parts = ([(s[:, :cut], v_sum[:cut])] if cut else []) + [
                    (last, v_sum[cut:])]
            else:
                parts = [(s, v_sum)]
            m_prev = m_scr[h]
            m_new = m_prev
            for sp, _ in parts:
                m_new = jnp.maximum(m_new, jnp.max(sp, axis=1, keepdims=True))
            pv = 0.0
            for sp, vp in parts:
                p = jnp.exp(sp - m_new[:, :1])
                pv = pv + jax.lax.dot_general(
                    p.astype(v.dtype), vp, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + pv
            acc = jnp.where(lane_head == h, alpha * acc + pv, acc)
            m_scr[h] = m_new
        acc_scr[...] = acc

    if pad and n_k > 1:
        @pl.when(ki < n_k - 1)
        def _body():
            step(False)

        @pl.when(ki == n_k - 1)
        def _last():
            step(True)
    else:
        step(bool(pad))

    @pl.when(ki == n_k - 1)
    def _finish():
        denom = jnp.ones_like(acc_scr)
        for h in range(heads):
            row = jnp.max(jnp.where(lane_head != h, l_scr[h], 0.0), axis=1,
                          keepdims=True)
            denom = jnp.where(lane_head == h, row, denom)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


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

    scale = 1.0 / (d ** 0.5)
    g = head_group(heads, kv_heads, d, causal, window, softcap)
    if g:  # one step: the 128 columns of g heads in q, k, v and out
        kernel = functools.partial(
            _grouped_kernel, heads=g, head_dim=d, scale=scale,
            block_k=block_k, n_k=n_k, kv_len=kv_len)
        grid = (b, heads // g, n_q, n_k)
        q_map = lambda i, h, qi, ki: (i, qi, h)  # noqa: E731
        kv_map = lambda i, h, qi, ki: (i, ki, h)  # noqa: E731
        q_cols = kv_cols = LANES
    else:
        kernel = functools.partial(
            _attn_kernel, heads=heads, group=heads // kv_heads, head_dim=d,
            scale=scale, causal=causal, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, n_k=n_k, kv_len=kv_len)
        grid = (b, n_q, n_k)
        q_map = lambda i, qi, ki: (i, qi, 0)  # noqa: E731
        kv_map = lambda i, qi, ki: (i, ki, 0)  # noqa: E731
        q_cols, kv_cols = width, kv_width
    itemsize = jnp.dtype(q.dtype).itemsize
    cost = cost_estimate(b, s, t, kv_len, width, kv_width, heads, block_q,
                         itemsize)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, q_cols), q_map),
            pl.BlockSpec((1, block_k, kv_cols), kv_map),
            pl.BlockSpec((1, block_k, kv_cols), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, q_cols), q_map),
        out_shape=jax.ShapeDtypeStruct((b, s, width), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g or heads, block_q, LANES), jnp.float32),
            pltpu.VMEM((g or heads, block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, q_cols), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(grid) - 1)
            + ("arbitrary",),
            vmem_limit_bytes=vmem_limit(block_q, block_k, q_cols, kv_cols,
                                        g or heads, itemsize)),
        cost_estimate=cost,
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
