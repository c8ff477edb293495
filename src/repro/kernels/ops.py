"""jit'd public wrappers around the Pallas kernels.

Handle arbitrary tensor shapes by flattening to the (rows, 128) lane
layout (zero-padding the tail), dispatching the kernel, and restoring the
original shape.

:func:`interpret_mode` is the one place that decides whether a Pallas
kernel runs compiled or in the interpreter: every kernel's
``interpret=None`` default resolves through it (compiled on TPU,
interpreted elsewhere so the kernels are validated on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import fused_bucket as _fb
from repro.kernels import fused_sgd as _fs
from repro.kernels import sign_compress as _sc

LANE = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode(interpret: bool | None) -> bool:
    """``interpret`` as given, or — for ``None`` — True off-TPU."""
    return (not _on_tpu()) if interpret is None else bool(interpret)


def _to_2d(x):
    flat = x.reshape(-1)
    pad = (-flat.size) % LANE
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANE), pad


def _from_2d(y, pad, shape):
    flat = y.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)


def fused_sgd(p, g, u, *, lr, momentum: float, weight_decay: float = 0.0,
              nesterov: bool = True, interpret: bool | None = None):
    """Fused SGD update; returns (p_new, u_new). lr may be traced."""
    p2, pad = _to_2d(p)
    g2, _ = _to_2d(g)
    u2, _ = _to_2d(u)
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    po, uo = _fs.fused_sgd_2d(p2, g2, u2, lr2, momentum=momentum,
                              weight_decay=weight_decay, nesterov=nesterov,
                              interpret=interpret)
    return _from_2d(po, pad, p.shape), _from_2d(uo, pad, u.shape)


def sign_compress(x, *, interpret: bool | None = None):
    """sign(x) * mean|x| (the Alg. 3/4 compressor).

    The scale divides by the TRUE element count (``x.size``), not the
    lane-padded buffer size, so tensors whose size is not a multiple of
    128 get an unbiased L1 scale (regression-tested at size 130).
    """
    x2, pad = _to_2d(x)
    total = _sc.abs_sum_2d(x2, interpret=interpret)
    scale = (total / x.size).reshape(1, 1)
    y = _sc.scale_sign_2d(x2, scale, interpret=interpret)
    return _from_2d(y, pad, x.shape).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Bucket-level entry points (flat parameter bus; see core/flatbuf.py)
# ---------------------------------------------------------------------------

def bucket_fused_sgd(p2, g2, u2, wd_row, *, lr, momentum: float,
                     weight_decay: float, nesterov: bool = True,
                     stats: bool = False, shards: int = 1,
                     interpret: bool | None = None):
    """One fused SGD launch over a whole (rows, 128) bucket.

    ``wd_row`` is the (rows, 1) f32 per-row weight-decay mask from
    ``flatbuf.wd_rows``. Returns (p2', u2'), or with ``stats=True``
    (p2', u2', sum(g^2), sum(||update||^2)) from the SAME launch
    (telemetry; zero extra HBM passes)."""
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    return _fb.fused_sgd_bucket_2d(p2, g2, u2, lr2, jnp.asarray(wd_row),
                                   momentum=momentum,
                                   weight_decay=weight_decay,
                                   nesterov=nesterov, stats=stats,
                                   shards=shards, interpret=interpret)


def bucket_sq_sum(x2, *, shards: int = 1, interpret: bool | None = None):
    """sum(x^2) over a bucket (f32) — one fused HBM pass."""
    return _fb.sq_sum_2d(x2, shards=shards, interpret=interpret)


def bucket_lars_norms(p2, g2, wd_row, *, weight_decay: float,
                      shards: int = 1, interpret: bool | None = None):
    """Per-row sum-of-squares of p and of g + wd*mask*p — one HBM pass.

    Returns ((rows, 1) f32, (rows, 1) f32); the per-layer LARS norms
    finish as one segmented reduction (see ``flatbuf.row_segments``).
    """
    return _fb.lars_row_norms_2d(p2, g2, jnp.asarray(wd_row),
                                 weight_decay=weight_decay, shards=shards,
                                 interpret=interpret)


def bucket_fused_lars(p2, g2, u2, wd_row, ratio_row, *, lr, momentum: float,
                      weight_decay: float, nesterov: bool = True,
                      stats: bool = False, shards: int = 1,
                      interpret: bool | None = None):
    """One fused LARS launch over a whole (rows, 128) bucket.

    ``ratio_row`` is the (rows, 1) f32 per-row trust ratio (1.0 on
    norm/bias rows, which take the plain LR). Returns (p2', u2'), or
    with ``stats=True`` (p2', u2', sum(g^2), sum(||update||^2)) from
    the SAME launch (telemetry; zero extra HBM passes)."""
    lr2 = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    return _fb.fused_lars_bucket_2d(p2, g2, u2, lr2, jnp.asarray(wd_row),
                                    ratio_row, momentum=momentum,
                                    weight_decay=weight_decay,
                                    nesterov=nesterov, stats=stats,
                                    shards=shards, interpret=interpret)


def bucket_sign_compress(x2, seg_ids, seg_sizes, *, shards: int = 1,
                         interpret: bool | None = None):
    """Segment-aware sign compressor over a bucket.

    ``seg_ids`` (rows,) int32 maps each row to its leaf segment and
    ``seg_sizes`` (num_segments,) f32 holds TRUE element counts (both
    static numpy constants from flatbuf) — per-leaf L1 scales come from
    ONE segmented reduction over per-row |x| sums, and padding (which
    contributes 0 to the sums) never biases a scale.

    Returns (y2 f32, scales (num_segments,) f32).
    """
    seg_ids = jnp.asarray(seg_ids)
    row_sums = _fb.row_abs_sum_2d(x2, shards=shards, interpret=interpret)
    totals = jax.ops.segment_sum(row_sums[:, 0], seg_ids,
                                 num_segments=int(seg_sizes.shape[0]))
    scales = totals / jnp.asarray(seg_sizes)
    y = _fb.scale_sign_rows_2d(x2, scales[seg_ids][:, None],
                               shards=shards, interpret=interpret)
    return y, scales


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float = 0.0, block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """GQA flash attention. q: (B, S, H, D); k, v: (B, S, KH, D)."""
    from repro.kernels.flash_attention import flash_attention_bhsd
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, -1, D)
    out = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                               scale=scale, block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
