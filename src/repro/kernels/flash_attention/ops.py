"""Jit'd public wrappers: shape padding + layout handling + CPU fallback
(interpret mode) for the flash attention kernel.

* :func:`flash_attention_packed` — the kernel's own sequence-major layout,
  q (B, S, H·D), k and v (B, T, KV·D);
* :func:`flash_attention` — heads-first (B, H, S, D) / (B, KV, T, D)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (cost_estimate,
                                                  flash_attention_fwd)


def _round_up(n, mult):
    return -(-n // mult) * mult


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _blocks(s, t, block_q, block_k):
    """A short sequence is one block, rounded up to the tiling of its
    dtype."""
    return min(block_q, _round_up(s, 16)), min(block_k, _round_up(t, 16))


def packed_cost(b, s, t, width, kv_width, heads, *, block_q=128,
                block_k=128, itemsize=2):
    """The kernel's cost estimate for :func:`flash_attention_packed` of
    these shapes, after its padding."""
    bq, bk = _blocks(s, t, block_q, block_k)
    return cost_estimate(b, _round_up(s, bq), _round_up(t, bk), t, width,
                         kv_width, heads, bq, itemsize)


@partial(
    jax.jit,
    static_argnames=("heads", "kv_heads", "causal", "window", "softcap",
                     "block_q", "block_k", "interpret"),
)
def flash_attention_packed(
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
) -> jnp.ndarray:
    s, t = q.shape[1], k.shape[1]
    bq, bk = _blocks(s, t, block_q, block_k)
    # padded queries are garbage rows sliced off below; padded keys are
    # masked in-kernel via kv_len.
    out = flash_attention_fwd(
        _pad_to(q, 1, bq), _pad_to(k, 1, bk), _pad_to(v, 1, bk),
        heads=heads, kv_heads=kv_heads, causal=causal, window=window,
        softcap=softcap, block_q=bq, block_k=bk, interpret=interpret,
        kv_len=t,
    )
    return out[:, :s]


@partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # (B, H, S, D)
    k: jnp.ndarray,  # (B, KV, T, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, h, s, d = q.shape

    def seq_major(x):
        return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)

    out = flash_attention_packed(
        seq_major(q), seq_major(k), seq_major(v), heads=h,
        kv_heads=k.shape[1], causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out.reshape(b, s, h, d).transpose(0, 2, 1, 3)
