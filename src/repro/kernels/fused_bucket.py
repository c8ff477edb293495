"""Pallas TPU kernels over flat parameter-bus buckets (see core/flatbuf).

One bucket is a contiguous (rows, 128) dtype-homogeneous buffer holding
many parameter leaves back to back, each starting on an (8, 128) tile
boundary.  These kernels replace the per-leaf launches of fused_sgd.py /
sign_compress.py with ONE launch per bucket:

  * ``fused_sgd_bucket_2d`` — the fused Nesterov-SGD update with a
    per-ROW weight-decay mask operand, so leaves with masked-off decay
    (norms/biases) share the launch with decayed matrices.
  * ``sq_sum_2d``           — masked sum of squares (global-norm clip).
  * ``row_abs_sum_2d``      — per-row |x| sums; the per-leaf L1 scales
    of the sign compressor finish as one tiny segmented reduction.
  * ``scale_sign_rows_2d``  — y = sign(x) * scale[row], the segment-
    aware second pass of the compressor.
  * ``lars_row_norms_2d``   — per-row sum-of-squares of p and of the
    decayed gradient g + wd*mask*p in ONE fused HBM pass; the per-layer
    LARS trust ratios finish as one tiny segmented reduction.
  * ``fused_lars_bucket_2d``— the LARS update with per-row trust-ratio
    and weight-decay-mask operands, so every layer of a bucket shares
    one launch (apply_lars used to dispatch per leaf).

Telemetry outputs (ISSUE 3): both update kernels accept ``stats=True``,
which adds two tiny per-grid-block partial sums to the SAME launch —
sum(g^2) of the raw gradient and sum((lr*step)^2) of the applied update
— so per-round grad-norm^2 / update-norm^2 telemetry costs zero extra
HBM passes: the operands are already streaming through VMEM for the
update; the stats are a few extra VPU ops plus one (8, 128) tile written
per grid block (``_partials_spec`` — Mosaic accepts no smaller output
block than one whole tile).

Reduction kernels mask the final partial grid block explicitly: the
grid over ``cdiv(rows, BLOCK_ROWS)`` reads out-of-bounds rows in its
last block and those values are undefined (NaN in interpret mode) — an
unmasked reduction silently folds them in once rows > BLOCK_ROWS.

Sharded sub-buckets (core/flatbuf sharding classes): a bucket whose row
dim is partitioned S-ways over mesh axes passes ``shards=S`` and the
launch takes PER-SHARD row counts — the block size is clamped (and
aligned, via gcd with the shard-local row count) so no grid block ever
straddles a shard boundary: each block's HBM traffic stays on one
device's memory, which is what lets the same launch geometry serve the
shard_map-per-device form on a real mesh.  ``shards=1`` (default) is
bit-identical to the pre-sub-bucket grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ops as _ops

LANE = 128
SUBLANE = 8
BLOCK_ROWS = 256          # (256, 128) f32 tile = 128 KiB per operand


def _partials_spec(n: int):
    """Out spec + shape for one f32 partial sum per grid block.

    The TPU lowering takes no output block smaller than one (8, 128)
    tile unless it spans the whole array, so each grid step writes its
    partial broadcast over a whole tile of an (n, 8, 128) array and
    :func:`_sum_partials` reads the n values back at ``[:, 0, 0]``."""
    return (pl.BlockSpec((1, SUBLANE, LANE), lambda i: (i, 0, 0)),
            jax.ShapeDtypeStruct((n, SUBLANE, LANE), jnp.float32))


def _write_partial(o_ref, value):
    o_ref[...] = jnp.broadcast_to(value, o_ref.shape)


def _sum_partials(out):
    """Sum of the per-block partials, in block order."""
    return out[:, 0, 0].sum()


def _block_rows(rows: int, shards: int) -> int:
    """Block size for a bucket launch: BLOCK_ROWS-clamped, and for
    SHARDED buckets (shards > 1) additionally aligned to evenly tile
    ONE shard's rows (shard-local rows are a SUBLANE multiple, so the
    gcd is >= 8) so no block straddles a shard boundary.  Replicated
    buckets keep the plain clamp — their final partial block is handled
    by the in-kernel row masking, exactly as before sub-buckets."""
    local = rows // max(shards, 1)
    br = min(BLOCK_ROWS, local)
    if shards > 1 and local % br:
        br = math.gcd(local, br)
    return br


def _row_mask(shape, block_idx: int, br: int, rows: int):
    """Boolean (br, ...) mask: True on rows that exist in the buffer."""
    rid = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + block_idx * br
    return rid < rows


def _sgd_kernel(lr_ref, wd_ref, p_ref, g_ref, u_ref, po_ref, uo_ref,
                *stat_refs, momentum: float, weight_decay: float,
                nesterov: bool, rows: int = 0, br: int = 0):
    lr = lr_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    if stat_refs:
        # raw-gradient norm^2 BEFORE decay (the telemetry signal); the
        # final partial grid block reads undefined out-of-bounds rows,
        # which the reductions must mask (cf. _sq_sum_kernel)
        mask = _row_mask(g.shape, pl.program_id(0), br, rows)
        gm = jnp.where(mask, g, 0.0)
        _write_partial(stat_refs[0], jnp.sum(gm * gm))
    if weight_decay:
        # wd_ref is the (br, 1) per-row mask: 1.0 on decayed leaves' rows
        g = g + (weight_decay * wd_ref[...]) * p
    u_new = momentum * u + g
    step = momentum * u_new + g if nesterov else u_new
    d = lr * step
    po_ref[...] = (p - d).astype(po_ref.dtype)
    uo_ref[...] = u_new.astype(uo_ref.dtype)
    if stat_refs:
        dm = jnp.where(mask, d, 0.0)
        _write_partial(stat_refs[1], jnp.sum(dm * dm))


@functools.partial(jax.jit, static_argnames=("momentum", "weight_decay",
                                             "nesterov", "stats", "shards",
                                             "interpret"))
def fused_sgd_bucket_2d(p, g, u, lr, wd_row, *, momentum: float,
                        weight_decay: float, nesterov: bool,
                        stats: bool = False, shards: int = 1,
                        interpret: bool | None = None):
    """One fused SGD launch over a whole bucket.

    p, g, u: (rows, 128) same dtype; lr: (1, 1) f32 (SMEM, may be
    traced); wd_row: (rows, 1) f32 weight-decay row mask.
    Returns (p', u'), or (p', u', sum(g^2), sum((lr*step)^2)) with
    ``stats=True`` — the two scalars ride the same launch (telemetry).
    """
    rows = p.shape[0]
    br = _block_rows(rows, shards)
    n = pl.cdiv(rows, br)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    mspec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    sspec, sshape = _partials_spec(n)
    out_specs = [spec, spec] + ([sspec, sspec] if stats else [])
    out_shape = [jax.ShapeDtypeStruct(p.shape, p.dtype),
                 jax.ShapeDtypeStruct(u.shape, u.dtype)]
    if stats:
        out_shape += [sshape, sshape]
    out = pl.pallas_call(
        functools.partial(_sgd_kernel, momentum=momentum,
                          weight_decay=weight_decay, nesterov=nesterov,
                          rows=rows, br=br),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), mspec,
                  spec, spec, spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_ops.interpret_mode(interpret),
    )(lr, wd_row, p, g, u)
    if stats:
        po, uo, gsq, usq = out
        return po, uo, _sum_partials(gsq), _sum_partials(usq)
    return out


def _sq_sum_kernel(x_ref, o_ref, *, rows, br):
    x = x_ref[...].astype(jnp.float32)
    x = jnp.where(_row_mask(x.shape, pl.program_id(0), br, rows), x, 0.0)
    _write_partial(o_ref, jnp.sum(x * x))


@functools.partial(jax.jit, static_argnames=("shards", "interpret"))
def sq_sum_2d(x, *, shards: int = 1, interpret: bool | None = None):
    """sum(x^2) over a bucket (f32 accumulate) — one HBM read."""
    rows = x.shape[0]
    br = _block_rows(rows, shards)
    n = pl.cdiv(rows, br)
    ospec, oshape = _partials_spec(n)
    out = pl.pallas_call(
        functools.partial(_sq_sum_kernel, rows=rows, br=br),
        grid=(n,),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0))],
        out_specs=ospec,
        out_shape=oshape,
        interpret=_ops.interpret_mode(interpret),
    )(x)
    return _sum_partials(out)


def _row_abs_sum_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    # per-row (lane-only) reduction: out-of-bounds rows in the final
    # partial block land on discarded output rows, so no masking needed
    o_ref[...] = jnp.sum(jnp.abs(x), axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("shards", "interpret"))
def row_abs_sum_2d(x, *, shards: int = 1, interpret: bool | None = None):
    """(rows, 1) f32 per-row |x| sums — one HBM read of the bucket."""
    rows = x.shape[0]
    br = _block_rows(rows, shards)
    return pl.pallas_call(
        _row_abs_sum_kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        interpret=_ops.interpret_mode(interpret),
    )(x)


def _lars_row_norms_kernel(wd_ref, p_ref, g_ref, pn_ref, gn_ref, *,
                           weight_decay: float):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + (weight_decay * wd_ref[...]) * p
    # per-row (lane-only) reductions: out-of-bounds rows of the final
    # partial grid block land on discarded output rows (cf. row_abs_sum)
    pn_ref[...] = jnp.sum(p * p, axis=1, keepdims=True)
    gn_ref[...] = jnp.sum(g * g, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("weight_decay", "shards", "interpret"))
def lars_row_norms_2d(p, g, wd_row, *, weight_decay: float, shards: int = 1,
                      interpret: bool | None = None):
    """Per-row sum-of-squares of p and of g + wd*mask*p, one HBM pass.

    Returns (p_sq, g_sq), each (rows, 1) f32. The per-layer LARS norms
    ||p||, ||g + wd*p|| finish as a segmented reduction over these rows
    (padding contributes exactly 0 while the padding-is-zero invariant
    holds; see flatbuf.valid_mask).
    """
    rows = p.shape[0]
    br = _block_rows(rows, shards)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    mspec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_lars_row_norms_kernel, weight_decay=weight_decay),
        grid=(pl.cdiv(rows, br),),
        in_specs=[mspec, spec, spec],
        out_specs=[mspec, mspec],
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=_ops.interpret_mode(interpret),
    )(wd_row, p, g)


def _lars_kernel(lr_ref, wd_ref, r_ref, p_ref, g_ref, u_ref, po_ref, uo_ref,
                 *stat_refs, momentum: float, weight_decay: float,
                 nesterov: bool, rows: int = 0, br: int = 0):
    lr = lr_ref[0, 0]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    if stat_refs:
        # raw-gradient norm^2 before decay/trust scaling (telemetry);
        # mask the final partial grid block (cf. _sgd_kernel)
        mask = _row_mask(g.shape, pl.program_id(0), br, rows)
        gm = jnp.where(mask, g, 0.0)
        _write_partial(stat_refs[0], jnp.sum(gm * gm))
    if weight_decay:
        g = g + (weight_decay * wd_ref[...]) * p
    # r_ref is the (br, 1) per-row trust ratio (1.0 on norm/bias rows)
    g = g * r_ref[...]
    u_new = momentum * u + g
    step = momentum * u_new + g if nesterov else u_new
    d = lr * step
    po_ref[...] = (p - d).astype(po_ref.dtype)
    uo_ref[...] = u_new.astype(uo_ref.dtype)
    if stat_refs:
        dm = jnp.where(mask, d, 0.0)
        _write_partial(stat_refs[1], jnp.sum(dm * dm))


@functools.partial(jax.jit, static_argnames=("momentum", "weight_decay",
                                             "nesterov", "stats", "shards",
                                             "interpret"))
def fused_lars_bucket_2d(p, g, u, lr, wd_row, ratio_row, *, momentum: float,
                         weight_decay: float, nesterov: bool,
                         stats: bool = False, shards: int = 1,
                         interpret: bool | None = None):
    """One fused LARS launch over a whole bucket.

    p, g, u: (rows, 128) same dtype; lr: (1, 1) f32; wd_row: (rows, 1)
    f32 decay mask; ratio_row: (rows, 1) f32 per-row trust ratio
    (trust * ||p|| / (||g + wd*p|| + eps) per layer, 1.0 on skip rows).
    Returns (p', u'), or (p', u', sum(g^2), sum((lr*step)^2)) with
    ``stats=True`` — the two scalars ride the same launch (telemetry).
    """
    rows = p.shape[0]
    br = _block_rows(rows, shards)
    n = pl.cdiv(rows, br)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    mspec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    sspec, sshape = _partials_spec(n)
    out_specs = [spec, spec] + ([sspec, sspec] if stats else [])
    out_shape = [jax.ShapeDtypeStruct(p.shape, p.dtype),
                 jax.ShapeDtypeStruct(u.shape, u.dtype)]
    if stats:
        out_shape += [sshape, sshape]
    out = pl.pallas_call(
        functools.partial(_lars_kernel, momentum=momentum,
                          weight_decay=weight_decay, nesterov=nesterov,
                          rows=rows, br=br),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)), mspec, mspec,
                  spec, spec, spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_ops.interpret_mode(interpret),
    )(lr, wd_row, ratio_row, p, g, u)
    if stats:
        po, uo, gsq, usq = out
        return po, uo, _sum_partials(gsq), _sum_partials(usq)
    return out


def _scale_sign_rows_kernel(x_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (jnp.sign(x) * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("shards", "interpret"))
def scale_sign_rows_2d(x, scale_row, *, shards: int = 1, interpret: bool | None = None):
    """y = sign(x) * scale_row (per-row scales; second compressor pass)."""
    rows = x.shape[0]
    br = _block_rows(rows, shards)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    mspec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    return pl.pallas_call(
        _scale_sign_rows_kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[spec, mspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=_ops.interpret_mode(interpret),
    )(x, scale_row)
