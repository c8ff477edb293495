"""Pallas TPU kernels for the signSGD sync compressor (paper Alg. 3/4).

Compression of the model difference Delta is sign(Delta) * mean|Delta|.
Two kernels:
  1. ``abs_sum``    — per-tile |x| partial sums (reduction tree finishes
                      in jnp; one HBM read of x).
  2. ``scale_sign`` — y = sign(x) * scale, scale in SMEM (second HBM pass).

Same (rows, 128) lane layout as fused_sgd.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops as _ops
from repro.kernels import fused_bucket as _fb

LANE = 128
BLOCK_ROWS = 256


def _abs_sum_kernel(x_ref, o_ref, *, rows, br):
    x = x_ref[...].astype(jnp.float32)
    # mask the final partial grid block: its out-of-bounds rows are
    # undefined (NaN in interpret mode) and an unmasked reduction folds
    # them in whenever rows is not a multiple of BLOCK_ROWS
    mask = _fb._row_mask(x.shape, pl.program_id(0), br, rows)
    _fb._write_partial(o_ref, jnp.sum(jnp.where(mask, jnp.abs(x), 0.0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def abs_sum_2d(x, *, interpret: bool | None = None):
    rows = x.shape[0]
    br = min(BLOCK_ROWS, rows)
    n = pl.cdiv(rows, br)
    ospec, oshape = _fb._partials_spec(n)
    out = pl.pallas_call(
        functools.partial(_abs_sum_kernel, rows=rows, br=br),
        grid=(n,),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0))],
        out_specs=ospec,
        out_shape=oshape,
        interpret=_ops.interpret_mode(interpret),
    )(x)
    return _fb._sum_partials(out)


def _scale_sign_kernel(s_ref, x_ref, o_ref):
    s = s_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (jnp.sign(x) * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def scale_sign_2d(x, scale, *, interpret: bool | None = None):
    rows = x.shape[0]
    br = min(BLOCK_ROWS, rows)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _scale_sign_kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=_ops.interpret_mode(interpret),
    )(scale, x)
