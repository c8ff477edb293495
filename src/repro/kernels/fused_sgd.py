"""Pallas TPU kernel: fused Nesterov-momentum SGD update.

The paper's inner loop (eq. 2) is H sequential SGD updates per worker;
at large H the optimizer update is a pure HBM-bandwidth workload
(read p,g,u; write p,u). XLA usually fuses this, but the Pallas kernel
makes the tiling explicit and fuses weight decay + momentum + Nesterov +
parameter update into a single HBM pass per tensor:

    g' = g + wd * p
    u' = mu * u + g'
    p' = p - lr * (mu * u' + g')      (nesterov)
    p' = p - lr * u'                  (heavy-ball)

Layout: operands are reshaped to (rows, LANE) with LANE=128 and tiled
(BLOCK_ROWS, 128) into VMEM — 3 input + 2 output tiles of 8x128 f32
sublanes, comfortably inside the ~16 MB/core VMEM budget while keeping
the VPU lanes full. ``lr`` arrives as a (1,1) SMEM scalar so a traced
learning-rate schedule does not force recompilation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops as _ops

LANE = 128
BLOCK_ROWS = 256          # (256, 128) f32 tile = 128 KiB per operand


def _kernel(lr_ref, p_ref, g_ref, u_ref, po_ref, uo_ref, *, momentum: float,
            weight_decay: float, nesterov: bool):
    lr = lr_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    u_new = momentum * u + g
    step = momentum * u_new + g if nesterov else u_new
    po_ref[...] = (p - lr * step).astype(po_ref.dtype)
    uo_ref[...] = u_new.astype(uo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("momentum", "weight_decay",
                                             "nesterov", "interpret"))
def fused_sgd_2d(p, g, u, lr, *, momentum: float, weight_decay: float,
                 nesterov: bool, interpret: bool | None = None):
    """p, g, u: (rows, 128) same dtype; lr: (1,1) f32. Returns (p', u')."""
    rows = p.shape[0]
    br = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, br),)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, momentum=momentum,
                          weight_decay=weight_decay, nesterov=nesterov),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(u.shape, u.dtype)],
        interpret=_ops.interpret_mode(interpret),
    )(lr, p, g, u)
