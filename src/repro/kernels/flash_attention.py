"""Pallas TPU flash attention (causal / sliding-window, online softmax).

The substrate's attention hot-spot. The pure-jnp chunked implementation
(models/layers.py) is the lowering-friendly default; this kernel is the
TPU fast path: one pass over KV per query block with the running
(m, l, acc) softmax state held in VMEM registers, MXU-aligned
(block_q x block_k x D) tiles, and block-level skipping of fully-masked
KV blocks (no causal-mask FLOP waste — matching the banded-area FLOP
model in the roofline).

Layout: grid = (batch*heads, Sq / block_q); per program the query block
is a (block_q, D) VMEM tile and K/V are (Sk, D) VMEM residents — sized
for Sk*D*2 tensors <= ~8 MB (Sk <= 8k at D=128, bf16). Longer sequences
use the jnp path (or an HBM/ANY double-buffered variant — future work).
Validated against models.layers.reference_attention in interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops as _ops

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
            window: int, block_k: int, seq_k: int):
    _, bq, d = q_ref.shape                       # blocks carry a leading 1
    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)[:, 0]

    nk = seq_k // block_k
    if causal:
        hi = jnp.minimum(((i + 1) * bq - 1) // block_k + 1, nk)
    else:
        hi = nk
    lo = (jnp.maximum((i * bq - window + 1) // block_k, 0)
          if (window and causal) else 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = jnp.ones((bq, block_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos[:, None]
        if window:
            mask &= q_pos[:, None] - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         scale: float = 0.0, block_q: int = 128,
                         block_k: int = 128, interpret: bool | None = None):
    """q: (BH, Sq, D); k, v: (BH, Sk, D). Returns (BH, Sq, D)."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    scale = scale or 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    while Sq % bq:
        bq -= 1
    bk = min(block_k, Sk)
    while Sk % bk:
        bk -= 1
    grid = (BH, Sq // bq)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          block_k=bk, seq_k=Sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_ops.interpret_mode(interpret),
    )(q, k, v)
