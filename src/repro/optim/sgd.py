"""SGD with Nesterov momentum + decoupled-by-mask weight decay.

This is the paper's *local* optimizer: one independent instance per
worker (momentum buffers live inside the per-worker stacked state, so
"local momentum", App. B.4.1, falls out of the vmap).

Two dispatch strategies:

* ``use_kernel=False`` — pure-jnp per-leaf reference update.
* ``use_kernel=True``  — the flat parameter bus: params/grads/momentum
  are packed into dtype buckets (core/flatbuf) and updated with ONE
  fused Pallas launch per bucket, with the weight-decay mask carried as
  a per-row operand.  The grad-clip global norm is likewise one fused
  sum-of-squares reduction per bucket instead of one per leaf.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import OptimConfig
from repro.utils import tree_map_pairs


def init_momentum(params):
    return jax.tree.map(jnp.zeros_like, params)


def clip_by_global_norm(grads, max_norm: float):
    if not max_norm:
        return grads
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)


def _leaf_update(p, g, u, skip_wd, *, lr, momentum, wd, nesterov):
    gf = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    if wd and not skip_wd:
        gf = gf + wd * pf
    u_new = momentum * u.astype(jnp.float32) + gf
    step = (momentum * u_new + gf) if nesterov else u_new
    p_new = pf - lr * step
    return p_new.astype(p.dtype), u_new.astype(u.dtype)


def _jnp_bucket_sgd(p, g, u, wd_row, *, lr, momentum, weight_decay,
                    nesterov, want_stats):
    """Pure-jnp bucket update, same op order as the fused kernel.

    The GSPMD-friendly form for buckets sharded under a mesh: a
    ``pallas_call`` is opaque to the partitioner and would force a
    dense gather of worker-/row-sharded operands, while these
    elementwise ops partition trivially (the stats sums lower to a
    shard-local reduce + scalar all-reduce)."""
    pf = p.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    gsq = jnp.sum(gf * gf) if want_stats else None
    if weight_decay:
        gf = gf + (weight_decay * wd_row) * pf
    u_new = momentum * uf + gf
    step = momentum * u_new + gf if nesterov else u_new
    d = lr * step
    out = ((pf - d).astype(p.dtype), u_new.astype(u.dtype))
    if want_stats:
        return out + (gsq, jnp.sum(d * d))
    return out


def apply_sgd_buckets(layout, pb, gb, ub, *, lr, momentum_coef: float,
                      weight_decay: float, nesterov: bool,
                      grad_clip: float = 0.0, want_stats: bool = False,
                      kernel: bool = True):
    """Bucket-in/bucket-out fused SGD: the resident-state hot path.

    ``pb``/``gb``/``ub`` are per-bucket (rows, 128) buffers laid out by
    ``layout`` (one launch per bucket; the grad-clip global norm is one
    fused sum-of-squares per bucket).  Performs ZERO pack/unpack — with
    state held resident across local steps (core/local_sgd) the flatten
    cost is paid once per sync round instead of once per step.

    ``kernel=False`` dispatches the same math as jnp elementwise ops —
    the GSPMD-friendly form for buckets sharded under a mesh (worker
    dim and, for sharded sub-buckets, the row dim), where an opaque
    Pallas call would force a dense gather of the operands.  The kernel
    form passes each bucket's shard count so launch grids take
    per-shard row counts (kernels/fused_bucket).

    Returns (pb', ub') as lists of buckets; with ``want_stats=True``
    returns (pb', ub', (grad_sq, update_sq)) where the two f32 scalars
    — sum over all buckets of ||g||^2 (post-clip) and ||Δp||^2 — come
    out of the SAME fused update launches (see kernels/fused_bucket),
    so telemetry adds zero extra full-state HBM passes.
    """
    from repro.core import flatbuf
    from repro.kernels import ops as kops

    if grad_clip:
        # grad buckets have exact-zero padding (the bucket view's
        # gradient writes leaves into a zero bucket), so the bucket norm
        # equals the per-leaf global norm
        if kernel:
            gn2 = sum(kops.bucket_sq_sum(g, shards=layout.bucket_shard_count(b))
                      for b, g in enumerate(gb))
        else:
            gn2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in gb)
        gn = jnp.sqrt(gn2)
        scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gn, 1e-12))
        gb = [(g * scale).astype(g.dtype) for g in gb]
    po, uo = [], []
    gsq = usq = jnp.float32(0.0)
    for b in range(layout.num_buckets):
        wd_row = flatbuf.wd_rows(layout, b)
        if kernel:
            out = kops.bucket_fused_sgd(pb[b], gb[b], ub[b], wd_row, lr=lr,
                                        momentum=momentum_coef,
                                        weight_decay=weight_decay,
                                        nesterov=nesterov, stats=want_stats,
                                        shards=layout.bucket_shard_count(b))
        else:
            out = _jnp_bucket_sgd(pb[b], gb[b], ub[b], jnp.asarray(wd_row),
                                  lr=lr, momentum=momentum_coef,
                                  weight_decay=weight_decay,
                                  nesterov=nesterov, want_stats=want_stats)
        if want_stats:
            p2, u2, bg, bu = out
            gsq = gsq + bg
            usq = usq + bu
        else:
            p2, u2 = out
        po.append(p2)
        uo.append(u2)
    if want_stats:
        return po, uo, (gsq, usq)
    return po, uo


def _apply_sgd_bucketed(params, grads, momentum, wd_mask, *, lr,
                        momentum_coef, weight_decay, nesterov, grad_clip):
    """Flat-bus path: O(#dtype buckets) kernel launches, not O(#leaves).

    Tree-in/tree-out wrapper around :func:`apply_sgd_buckets` — it packs
    and unpacks around every call, which the resident-state path in
    core/local_sgd avoids entirely.
    """
    from repro.core import flatbuf

    layout = flatbuf.build_layout(params, wd_mask=wd_mask)
    po, uo = apply_sgd_buckets(
        layout, flatbuf.flatten(layout, params), flatbuf.flatten(layout, grads),
        flatbuf.flatten(layout, momentum), lr=lr, momentum_coef=momentum_coef,
        weight_decay=weight_decay, nesterov=nesterov, grad_clip=grad_clip)
    return flatbuf.unflatten(layout, po), flatbuf.unflatten(layout, uo)


def apply_sgd(params, grads, momentum, *, lr, momentum_coef: float,
              weight_decay: float, nesterov: bool, wd_mask=None,
              grad_clip: float = 0.0, use_kernel: bool = False):
    if wd_mask is None:
        wd_mask = jax.tree.map(lambda _: False, params)
    if use_kernel:
        return _apply_sgd_bucketed(params, grads, momentum, wd_mask, lr=lr,
                                   momentum_coef=momentum_coef,
                                   weight_decay=weight_decay,
                                   nesterov=nesterov, grad_clip=grad_clip)
    grads = clip_by_global_norm(grads, grad_clip)

    def upd(p, g, u, skip):
        return _leaf_update(p, g, u, skip, lr=lr, momentum=momentum_coef,
                            wd=weight_decay, nesterov=nesterov)
    return tree_map_pairs(upd, params, grads, momentum, wd_mask)
