"""Local SGD / Post-local SGD / Hierarchical local SGD — the paper's core.

Representation: every parameter (and momentum buffer) carries a leading
worker dim ``W`` sharded over the layout's ``worker_axes``. Local steps
are the single-worker update lifted with ``jax.vmap`` — GSPMD therefore
emits *no* cross-worker collectives during the local phase (eq. 2, inner
loop). Synchronization is a (possibly grouped) mean over the worker dim —
one all-reduce over the worker axes, amortized ``1/H`` (Alg. 1 line 9/10).

Synchronization is driven by a :class:`~repro.core.syncplan.SyncPlan`
(ISSUE 5): ``sync(state, plan=plan, scope=...)`` executes the plan's
staged schedule (pack -> collective -> apply per sub-bucket), and the
topology is a DECLARED property of the plan — ``hierarchical(block)``
(Alg. 5) averages within blocks of consecutive workers at scope
``"block"``; with ``worker_axes = ('pod','data')`` a block = one pod,
so inner syncs ride intra-pod ICI and outer syncs the inter-pod links —
exactly the paper's Figure 17 mapping.  The legacy
``sync(state, group=block_size)`` kwargs survive as a shim that builds
the equivalent plan per call (``group != W`` deprecates).

Variants carried in state:
* local momentum  — per-worker buffers inside the vmap (App. B.4.1)
* global momentum — applied to the averaged model difference at sync
* sign / EF-sign  — compress per-worker model differences before the
  average (Alg. 3 / Alg. 4)

Resident bucket state (ISSUE 2/4): with ``use_kernel=True`` the state
fields hold ``flatbuf.BucketState`` buffers instead of pytrees — for
EVERY layout, including within-worker-sharded (FSDP/TP) ones, whose
leaves ride (dtype, sharding-class) sub-buckets (``flatbuf.shard_classes``)
kept row-sharded on the bus.  Local steps differentiate the loss THROUGH the
bucket view — ``flatbuf.view`` is part of the forward graph, and its
gradient writes each leaf's grad into its slot of one grad bucket — and
the fused optimizer
consumes/produces buckets directly: zero explicit flatten/unflatten
between sync boundaries, vs 10 full-state pack/unpack HBM passes per
step on the tree-in/tree-out kernel path.  Sync (mean / sign / EF-sign
/ wire-pack) also runs straight on buckets.  The pytree view exists
only at explicit boundaries: ``unpack_state`` (eval/checkpoint/logging)
and ``pack_state`` (re-entry after host-side surgery).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LocalSGDConfig, OptimConfig, RunConfig
from repro.core import compression as comp
from repro.core import flatbuf
from repro.core import noise as noise_mod
from repro.core import syncplan as splan
from repro.core.schedule import lr_at
from repro.core.syncplan import resolve_comp_modes  # re-export (pre-plan API)
from repro.optim.lars import apply_lars, apply_lars_buckets
from repro.optim.sgd import apply_sgd, apply_sgd_buckets, init_momentum
from repro.telemetry import stats as tstats


@jax.tree_util.register_dataclass
@dataclass
class LocalSGDState:
    params: Any          # stacked (W, ...)
    momentum: Any        # stacked (W, ...)
    anchor: Any          # single-copy tree (last synced model) or None
    global_u: Any        # single-copy tree or None
    ef_memory: Any       # stacked (W, ...) or None
    step: Any            # () int32
    rng: Any             # PRNGKey
    stats: Any = None    # telemetry.StatsAccumulator or None (ISSUE 3)


def needs_anchor(cfg: LocalSGDConfig) -> bool:
    return cfg.global_momentum > 0 or cfg.sync_compression != "none"


def stack_tree(tree, W: int):
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (W,) + p.shape), tree)


# ---------------------------------------------------------------------------
# Resident <-> pytree state conversion (the ONLY boundaries at which the
# pytree view of a resident state exists; see flatbuf.BucketState)
# ---------------------------------------------------------------------------

def is_resident(state: "LocalSGDState") -> bool:
    return flatbuf.is_bucket_state(state.params)


def unpack_state(state: "LocalSGDState") -> "LocalSGDState":
    """Materialize the pytree view of a resident state (no-op otherwise).

    The boundary for eval/checkpoint/logging and the reference oracle in
    the trajectory-equivalence tests.
    """
    up = lambda x: x.unpack() if flatbuf.is_bucket_state(x) else x
    return LocalSGDState(params=up(state.params), momentum=up(state.momentum),
                         anchor=up(state.anchor), global_u=up(state.global_u),
                         ef_memory=up(state.ef_memory), step=state.step,
                         rng=state.rng, stats=state.stats)


def pack_state(state: "LocalSGDState", *, wd_mask=None,
               shard_classes=None) -> "LocalSGDState":
    """Re-enter resident bucket form from a pytree state.

    ``wd_mask`` is recorded in the params layout (the fused optimizer
    reads the per-row decay mask from it); ``shard_classes`` re-enters
    the (dtype, sharding-class) sub-bucket form of a sharded layout
    (``flatbuf.shard_classes``).  EVERY field is packed with
    the params layout's bucket GEOMETRY — the resident sync zips
    anchor/global_u/ef buckets against params buckets one-to-one — with
    the actual per-bucket dtype preserved: ef_memory/global_u leaves
    promote to f32 after the first sync (exactly as the per-leaf
    reference promotes), and re-packing must neither demote them nor
    collapse them into a different bucket structure.
    """
    if is_resident(state):
        return state
    layout = flatbuf.build_layout(state.params, wd_mask=wd_mask, leading=1,
                                  shard_classes=shard_classes)

    def pack(tree, leading):
        if tree is None:
            return None
        dts = [np.dtype(l.dtype).name for l in jax.tree.leaves(tree)]
        if dts == [s.dtype for s in layout.slots]:
            return flatbuf.BucketState.pack(tree, layout=layout,
                                            leading=leading)
        # dtype-promoted field: keep params bucket geometry, carry the
        # promoted dtype per bucket (must be uniform within a bucket)
        per_bucket = []
        for b in range(layout.num_buckets):
            bd = {dts[s.index] for s in layout.bucket_slots(b)}
            if len(bd) != 1:
                raise ValueError(
                    f"cannot pack mixed dtypes {sorted(bd)} into params "
                    f"bucket {b} ({layout.bucket_dtypes[b]})")
            per_bucket.append(bd.pop())
        bufs = flatbuf.flatten(layout, tree, leading=leading,
                               bucket_dtypes=tuple(per_bucket))
        return flatbuf.BucketState(layout, tuple(bufs), leading=leading)

    return LocalSGDState(params=flatbuf.BucketState.pack(state.params,
                                                         layout=layout,
                                                         leading=1),
                         momentum=pack(state.momentum, 1),
                         anchor=pack(state.anchor, 0),
                         global_u=pack(state.global_u, 0),
                         ef_memory=pack(state.ef_memory, 1),
                         step=state.step, rng=state.rng, stats=state.stats)


def mean_params(state: "LocalSGDState"):
    """Single-copy pytree view of the worker-averaged model — works on
    both resident and pytree states (eval boundary)."""
    if is_resident(state):
        return flatbuf.unflatten(state.params.layout,
                                 [b.mean(axis=0) for b in state.params.buckets])
    return jax.tree.map(lambda p: p.mean(axis=0), state.params)


def resident_eligible(use_kernel: bool, bucket_sync: bool,
                      bucketable=None) -> bool:
    """Single source of truth for the resident-mode default: the kernel
    flat bus must be on and sync bucketized (an explicit
    bucket_sync=False keeps the per-leaf oracle per-leaf all the way).
    Within-worker-sharded leaves no longer disqualify residency — they
    ride their own (dtype, sharding-class) sub-bucket
    (flatbuf.shard_classes), so FSDP/TP layouts take the same resident
    path as replicated ones.  build_train uses the same predicate so
    its sharding specs always agree with the state structure
    make_local_sgd returns.  ``bucketable`` is accepted for backward
    compatibility and ignored."""
    del bucketable
    return bool(use_kernel and bucket_sync)


def group_mean(x, group: int):
    """Mean over blocks of ``group`` consecutive workers, broadcast back."""
    W = x.shape[0]
    assert W % group == 0, (W, group)
    if group == 1:
        return x
    xg = x.reshape(W // group, group, *x.shape[1:])
    m = xg.mean(axis=1, keepdims=True).astype(x.dtype)
    return jnp.broadcast_to(m, xg.shape).reshape(x.shape)


def _bucketed_map(tree, bucketable, bucket_fn, leaf_fn, leaf_args=None):
    """Shared scaffold of the bucketized sync paths.

    Stacked (W, ...) leaves marked bucketable ride the flat bus:
    ``bucket_fn(buf, layout, j)`` is applied to each (W, rows, 128)
    dtype bucket (whether the result keeps the worker dim is inferred
    from its rank). The rest take ``leaf_fn(leaf, arg)`` per leaf.
    ``bucketable`` is an optional bool pytree; leaves marked False
    (within-worker sharded — flattening them would force a gather) stay
    on the per-leaf path.
    """
    from repro.core import flatbuf

    leaves, treedef = jax.tree.flatten(tree)
    flags = (jax.tree.leaves(bucketable) if bucketable is not None
             else [True] * len(leaves))
    args = (jax.tree.leaves(leaf_args) if leaf_args is not None
            else [None] * len(leaves))
    assert len(flags) == len(leaves) and len(args) == len(leaves)
    out: list = [None] * len(leaves)
    on = [i for i, m in enumerate(flags) if m]
    for i, m in enumerate(flags):
        if not m:
            out[i] = leaf_fn(leaves[i], args[i])
    if on:
        sub = [leaves[i] for i in on]
        layout = flatbuf.build_layout(sub, leading=1)
        bufs = flatbuf.flatten(layout, sub, leading=1)
        res = [bucket_fn(b, layout, j) for j, b in enumerate(bufs)]
        vals = flatbuf.unflatten(layout, res,
                                 leading=res[0].ndim - bufs[0].ndim + 1)
        for i, v in zip(on, vals):
            out[i] = v
    return jax.tree.unflatten(treedef, out)


def bucket_group_mean(params, group: int, bucketable=None):
    """group_mean over dtype buckets: one mean per bucket, O(#dtypes)
    collectives under GSPMD instead of one per leaf."""
    return _bucketed_map(params, bucketable,
                         lambda b, lay, j: group_mean(b, group),
                         lambda x, _: group_mean(x, group))


def bucket_worker_mean(delta, bucketable=None):
    """mean over the worker dim per dtype bucket (dense sync payload):
    one collective per bucket under GSPMD instead of one per leaf."""
    return _bucketed_map(delta, bucketable,
                         lambda b, lay, j: b.mean(axis=0),
                         lambda x, _: x.mean(axis=0))


def make_packed_mean(mesh, worker_axes: tuple[str, ...]):
    """1-bit wire mean over workers via an explicit shard_map boundary.

    GSPMD sharding hints are insufficient here: propagation keeps placing
    the gather on the uncompressed f32 delta (measured 12-23x the ideal
    wire bytes; EXPERIMENTS §Perf hillclimb 3). shard_map pins the
    collective: pack signs shard-local, `lax.all_gather` the uint8
    payload over the worker axes, unpack + average locally. Within-worker
    ('model') sharding stays GSPMD-managed via partial-auto mode.
    """
    from jax.sharding import PartitionSpec as P

    axis = worker_axes if len(worker_axes) > 1 else worker_axes[0]

    def packed_mean(d, pack_axis: int = -1):
        W = d.shape[0]

        def f(local):                     # (1, *shape_local)
            packed, scale = comp.pack_signs(local, axis=pack_axis)
            allp = jax.lax.all_gather(packed, axis)       # (W, 1, ...)
            alls = jax.lax.all_gather(scale, axis)
            allp = allp.reshape((W,) + packed.shape[1:])
            alls = alls.reshape(W)
            return comp.unpack_signs(allp, alls, local.shape[1:],
                                     axis=pack_axis).mean(axis=0)

        g = jax.shard_map(f, mesh=mesh, in_specs=P(axis), out_specs=P(),
                          axis_names=set(worker_axes), check_vma=False)
        return g(d)

    return packed_mean


def _cls_spec(cls: tuple[str, ...]):
    """PartitionSpec row entry for a bucket's sharding class: None for
    the replicated class, the bare axis name for a single-axis class,
    the tuple otherwise (shared by the per-class and coalesced wire
    packs so their sharding-spec mapping can never diverge)."""
    return None if not cls else (cls[0] if len(cls) == 1 else cls)


def make_packed_mean_flat(mesh, worker_axes: tuple[str, ...]):
    """Bucket-level 1-bit wire mean: ONE uint8 all_gather (+ one tiny
    f32 scale gather) per sub-bucket instead of one pair per leaf.

    The bucket is a contiguous (W, rows, 128) buffer (core/flatbuf);
    signs pack 8-per-uint8 along the 128-lane dim (always unsharded),
    per-leaf L1 scales come from one segmented reduction over row |x|
    sums, and unpack + averaging stay shard-local after the gather.

    SHARDED sub-buckets (bucket_class != ()): the row dim is
    partitioned over the class's mesh axes, so the shard_map goes
    manual over worker AND shard axes — each device packs its own
    (local_rows, 128) block, the payload gather runs over the WORKER
    axes only (per-device wire bytes scale with shard-local rows, not
    the gathered leaf), and the per-leaf scale totals cross shards via
    one (num_segments,)-sized psum.  The synced result comes back
    row-sharded over the same axes: the full leaf is never gathered.
    """
    from jax.sharding import PartitionSpec as P

    axis = worker_axes if len(worker_axes) > 1 else worker_axes[0]

    def packed_mean_flat(bucket, layout, b):
        W = bucket.shape[0]
        cls = layout.bucket_class(b)
        seg_ids_j = jnp.asarray(flatbuf.row_segments_local(layout, b))
        sizes_j = jnp.asarray(flatbuf.segment_sizes(layout, b))
        cls_spec = _cls_spec(cls)

        def f(local):                     # (1, local_rows, 128)
            x = local.astype(jnp.float32)[0]
            packed, scales = comp.pack_bucket_signs(x, seg_ids_j, sizes_j,
                                                    psum_axes=cls)
            allp = jax.lax.all_gather(packed, axis)             # uint8 on wire
            alls = jax.lax.all_gather(scales, axis)
            allp = allp.reshape((W,) + packed.shape)
            alls = alls.reshape(W, -1)
            return comp.unpack_bucket_signs(allp, alls, seg_ids_j).mean(axis=0)

        # fully manual over ALL mesh axes: the in_specs place the
        # worker dim and the class's row sharding; mesh axes outside
        # worker+class replicate the (cheap, shard-local) pack/unpack
        # work, the payload gather runs over the worker axes only, and
        # the scale totals psum over the class axes only
        g = jax.shard_map(f, mesh=mesh, in_specs=P(axis, cls_spec),
                          out_specs=P(cls_spec), check_vma=False)
        return g(bucket)

    return packed_mean_flat


def _packed_mean_flat_local(bucket, layout, b):
    """Meshless equivalent of make_packed_mean_flat (CPU tests): the
    same pack/unpack helpers, vmapped over workers instead of gathered.
    Sharded sub-buckets need no special casing here — the TILED segment
    map makes one segment_sum over all rows produce the same global
    per-leaf totals the mesh form assembles via its cross-shard psum."""
    seg_ids_j = jnp.asarray(flatbuf.row_segments(layout, b))
    sizes_j = jnp.asarray(flatbuf.segment_sizes(layout, b))
    x = bucket.astype(jnp.float32)                              # (W, rows, 128)
    packed, scales = jax.vmap(
        lambda xw: comp.pack_bucket_signs(xw, seg_ids_j, sizes_j))(x)
    return comp.unpack_bucket_signs(packed, scales, seg_ids_j).mean(axis=0)


def make_packed_mean_coalesced(mesh, worker_axes: tuple[str, ...]):
    """Coalesced 1-bit wire mean: ONE uint8 payload all_gather (+ one
    f32 scale gather) per DTYPE, shared by sub-buckets of different
    sharding classes (the multi-class wire-pack ROADMAP item; used by
    ``SyncPlan`` stages with ``coalesced=True``).

    Each device packs every sub-bucket's shard-local (local_rows, 128)
    block exactly as :func:`make_packed_mean_flat` does (including the
    per-class (num_segments,)-sized cross-shard scale psum), then
    CONCATENATES the packed uint8 rows — already materialized,
    shard-local, so the merge is a free copy of packed bytes, never a
    dense gather — and gathers the combined payload over the WORKER
    axes once.  Unpack splits the gathered rows back per bucket, so the
    result is bitwise-identical to per-class gathers: concat/split move
    no values.
    """
    from jax.sharding import PartitionSpec as P

    axis = worker_axes if len(worker_axes) > 1 else worker_axes[0]

    def packed_mean_coalesced(bufs, layout, bids):
        W = bufs[0].shape[0]
        segs = [jnp.asarray(flatbuf.row_segments_local(layout, b))
                for b in bids]
        sizes = [jnp.asarray(flatbuf.segment_sizes(layout, b)) for b in bids]
        classes = [layout.bucket_class(b) for b in bids]
        lrows = [layout.bucket_local_rows(b) for b in bids]
        nsegs = [len(layout.bucket_slots(b)) for b in bids]

        def f(*locals_):                  # one (1, local_rows_b, 128) per b
            packs, scs = [], []
            for x, sg, sz, cls in zip(locals_, segs, sizes, classes):
                pk, sc = comp.pack_bucket_signs(x.astype(jnp.float32)[0],
                                                sg, sz, psum_axes=cls)
                packs.append(pk)
                scs.append(sc)
            payload = (packs[0] if len(packs) == 1
                       else jnp.concatenate(packs, axis=0))
            scales = scs[0] if len(scs) == 1 else jnp.concatenate(scs, axis=0)
            allp = jax.lax.all_gather(payload, axis)      # uint8 on wire
            alls = jax.lax.all_gather(scales, axis)
            allp = allp.reshape((W,) + payload.shape)
            alls = alls.reshape(W, -1)
            outs, ro, so = [], 0, 0
            for sg, r, ns in zip(segs, lrows, nsegs):
                db = comp.unpack_bucket_signs(allp[:, ro:ro + r],
                                              alls[:, so:so + ns], sg)
                outs.append(db.mean(axis=0))
                ro += r
                so += ns
            return tuple(outs)

        # fully manual over ALL mesh axes, as make_packed_mean_flat:
        # each class's row sharding rides its own in/out spec, the
        # payload gather runs over the worker axes only
        g = jax.shard_map(f, mesh=mesh,
                          in_specs=tuple(P(axis, _cls_spec(c))
                                         for c in classes),
                          out_specs=tuple(P(_cls_spec(c)) for c in classes),
                          check_vma=False)
        return list(g(*bufs))

    return packed_mean_coalesced


def _packed_mean_coalesced_local(bufs, layout, bids):
    """Meshless fallback of :func:`make_packed_mean_coalesced`: the same
    per-bucket pack/unpack math bucket by bucket (there is no wire to
    coalesce on CPU) — values identical to the mesh form, which only
    concatenates the already-packed payloads."""
    return [_packed_mean_flat_local(x, layout, b)
            for x, b in zip(bufs, bids, strict=True)]


def bucket_packed_mean(delta, bucketable=None, *, flat_fn=None,
                       leaf_fn=None, axes_tree=None):
    """Wire-pack the stacked delta through the flat bus.

    Bucketable leaves ride one packed gather per dtype bucket via
    ``flat_fn`` (``make_packed_mean_flat``; meshless fallback when
    None); the rest use the per-leaf ``leaf_fn`` with its sharding-
    derived pack axis. Returns the single-copy averaged tree.
    """
    from repro.core import flatbuf

    flat_fn = flat_fn or _packed_mean_flat_local
    if leaf_fn is None:
        def leaf_fn(d, axis=-1):
            packed, scale = comp.pack_signs(d, axis=axis)
            return comp.unpack_signs(packed, scale, d.shape[1:],
                                     axis=axis).mean(axis=0)
    if axes_tree is None:
        axes_tree = jax.tree.map(lambda _: -1, delta)
    return _bucketed_map(
        delta, bucketable,
        lambda b, lay, j: flat_fn(b, lay, j),
        lambda d, axis: leaf_fn(d, -1 if axis is None else axis),
        leaf_args=axes_tree)


def pack_axes_tree(specs, layout):
    """Per-leaf pack axis: the largest UNSHARDED dim of the stacked leaf
    (offset +1 for the worker dim). Falls back to the last dim.

    "Unsharded" comes from the EFFECTIVE spec rules
    (``MeshLayout.dim_shards``, as the classifier and partition specs
    use), so a dim whose rule is dropped (uneven, or deduped
    first-wins) is correctly available for packing.
    """
    from repro.models import base as mbase

    def pick(ps: "mbase.ParamSpec"):
        best, best_size = -1, -1
        eff = layout.dim_shards(ps.axes, ps.shape)
        for i, (r, n) in enumerate(zip(eff, ps.shape)):
            sharded = r is not None and layout.axis_size(r) > 1
            if not sharded and n >= 8 and n > best_size:
                best, best_size = i + 1, n
        return best if best >= 1 else -1

    return jax.tree.map(pick, specs, is_leaf=mbase.is_spec)


def _plan_for_call(state, *, group, compression, plan, scope, W: int,
                   ls: LocalSGDConfig, anchored: bool):
    """Resolve one ``sync`` call to a (:class:`~repro.core.syncplan.
    SyncPlan`, scope) pair.

    The modern call passes ``plan=`` (built once by
    ``syncplan.make_sync_plan`` / ``launch.steps.build_train``) and a
    ``scope``.  The legacy kwargs survive as a back-compat shim: a bare
    ``sync(state)`` or ``sync(state, compression=...)`` silently builds
    a flat plan per call (same modes, same collectives — trajectories
    stay bitwise-identical), while ``sync(state, group=g)`` with
    ``g != W`` is DEPRECATED and builds a ``hierarchical(g)`` plan whose
    block stages reproduce the old grouped mean exactly.
    """
    if plan is not None:
        if group is not None or compression is not None:
            raise ValueError("pass either plan= or the legacy group=/"
                             "compression= kwargs, not both; rewrite modes "
                             "via plan.with_modes / PlanDelta")
        return plan, (scope or "global")
    g = group or W
    if group is not None and g != W:
        warnings.warn(
            "sync(state, group=...) is deprecated; declare the topology "
            "once via make_sync_plan(..., topology=hierarchical(group)) and "
            "call sync(state, plan=plan, scope='block')",
            DeprecationWarning, stacklevel=3)
    layout = (state.params.layout if flatbuf.is_bucket_state(state.params)
              else flatbuf.build_layout(state.params, leading=1))
    topo = splan.hierarchical(g) if g != W else splan.flat()
    p = splan.make_sync_plan(
        layout, topology=topo,
        compression=(compression if compression is not None
                     else ls.sync_compression),
        num_workers=W, wire_pack=ls.wire_pack, coalesce=ls.sync_coalesce,
        anchored=anchored)
    return p, ("block" if g != W else (scope or "global"))


def _sumsq(x, *, from_axis: int = 0):
    """f32 sum of squares over all dims from ``from_axis`` on (telemetry)."""
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf, axis=tuple(range(from_axis, x.ndim)))


def _tree_sumsq_w(tree):
    """(W,) per-worker sum of squares over all leaves of a stacked tree."""
    return sum(_sumsq(l, from_axis=1) for l in jax.tree.leaves(tree))


def make_local_sgd(run: RunConfig, loss_fn: Callable, *, num_workers: int,
                   wd_mask=None, use_kernel: bool = False,
                   packed_mean_fn: Callable | None = None,
                   packed_mean_flat_fn: Callable | None = None,
                   packed_mean_coalesced_fn: Callable | None = None,
                   bucket_sync: bool = True, bucketable=None,
                   shard_classes=None,
                   resident: bool | None = None,
                   sharded: bool | None = None,
                   telemetry: bool = False,
                   speculate_compression: bool = False):
    """Build (init, local_step, sync) for a single-worker ``loss_fn``.

    loss_fn(params, batch) -> (loss, metrics dict). The returned
    ``local_step`` takes per-worker-stacked params/batch.

    ``bucket_sync`` routes the sync averages through the flat parameter
    bus (one collective per sub-bucket; core/flatbuf) —
    ``bucket_sync=False`` keeps the per-leaf path (used by the
    equivalence tests). ``bucketable`` marks within-worker-sharded
    leaves that must stay per-leaf ON THE NON-RESIDENT TREE PATH (its
    on-the-fly layouts are always replicated); ``packed_mean_flat_fn``
    is the mesh-pinned bucket wire-pack from
    :func:`make_packed_mean_flat`.

    ``shard_classes`` is the per-leaf :class:`flatbuf.ShardClass`
    pytree (``flatbuf.shard_classes(specs, layout)``): the resident
    path buckets leaves per (dtype, sharding class), so FSDP/TP
    layouts get the same resident state, one-launch-per-bucket
    optimizer, and one-worker-collective-per-bucket sync as replicated
    layouts — the per-leaf fallback is gone from the main training
    flow.

    ``resident`` holds the optimizer state IN bucket form across local
    steps (flatbuf.BucketState; see module docstring).  Default: on
    whenever ``use_kernel`` and ``bucket_sync`` are set (an explicit
    ``bucket_sync=False`` keeps the per-leaf oracle per-leaf all the
    way).  The resident ``init`` returns a state whose params/momentum
    (and anchor/global_u/ef_memory when present) are BucketStates; use
    ``unpack_state`` at eval/checkpoint/logging boundaries.

    ``sharded`` marks the state as mesh-sharded (set by build_train);
    the resident path then uses the GSPMD-friendly jnp forms for BOTH
    the optimizer update and the compressor instead of Pallas launches,
    whose opaque calls on sharded operands would force a dense gather.
    Default: inferred from whether a mesh-pinned wire pack is wired in.

    ``telemetry`` carries a ``telemetry.StatsAccumulator`` in
    ``state.stats`` (ISSUE 3): per-step grad/update norms (fused into
    the already-launched optimizer kernels on the resident path), a
    pre-/post-mean norm pair and compression error at each global sync.
    Telemetry is a pure observer — the parameter trajectory is bitwise
    identical with it on or off.  ``speculate_compression`` additionally
    measures the WOULD-BE sign-compression error on uncompressed anchor
    syncs (one extra compressor pass per sync, O(1/H)) so the
    ``auto_compress`` controller can decide when to start compressing.

    ``sync`` accepts a static ``compression`` override (see
    :func:`resolve_comp_modes`) so the controller can switch
    mean -> sign -> EF-sign at runtime; overrides other than the config
    default require the config to have allocated the anchor (and EF
    memory for ``ef_sign``) up front.
    """
    ls = run.local_sgd
    opt = run.optim
    W = num_workers
    global_batch = run.shape.global_batch

    if resident is None:
        resident = resident_eligible(use_kernel, bucket_sync)
    if resident:
        return _make_resident_local_sgd(
            run, loss_fn, num_workers=W, wd_mask=wd_mask,
            packed_mean_flat_fn=packed_mean_flat_fn,
            packed_mean_coalesced_fn=packed_mean_coalesced_fn,
            shard_classes=shard_classes,
            sharded=(packed_mean_flat_fn is not None if sharded is None
                     else sharded),
            telemetry=telemetry, speculate_compression=speculate_compression)

    def init(rng, params_single) -> LocalSGDState:
        params = stack_tree(params_single, W)
        return LocalSGDState(
            params=params,
            momentum=init_momentum(params),
            anchor=jax.tree.map(jnp.copy, params_single) if needs_anchor(ls) else None,
            global_u=(jax.tree.map(jnp.zeros_like, params_single)
                      if ls.global_momentum > 0 else None),
            ef_memory=(init_momentum(params) if ls.sync_compression == "ef_sign"
                       else None),
            step=jnp.int32(0),
            rng=rng,
            stats=tstats.init_stats(W, 1) if telemetry else None,
        )

    def _worker_step(p, u, batch, rng, lr, step):
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
        if opt.noise_eta > 0:
            g = noise_mod.isotropic_noise(g, rng, step=step, eta=opt.noise_eta,
                                          gamma=opt.noise_gamma)
        gsq = usq = None
        if telemetry:
            # pure observation: the update path below is untouched, so
            # telemetry cannot perturb the trajectory by construction.
            # grad_sq reports the APPLIED (post-clip) gradient norm^2,
            # computed analytically from the raw norm — clipping scales
            # the whole vector, so ||clip(g)||^2 = min(||g||, c)^2.
            gn2 = sum(_sumsq(l) for l in jax.tree.leaves(g))
            if opt.grad_clip and opt.optimizer != "lars":
                gsq = jnp.minimum(gn2, jnp.float32(opt.grad_clip) ** 2)
            else:
                gsq = gn2
        p0 = p
        if opt.optimizer == "lars":
            p, u = apply_lars(p, g, u, lr=lr, trust=opt.lars_trust,
                              momentum_coef=ls.local_momentum,
                              weight_decay=opt.weight_decay,
                              nesterov=ls.nesterov, wd_mask=wd_mask,
                              use_kernel=use_kernel)
        else:
            p, u = apply_sgd(p, g, u, lr=lr, momentum_coef=ls.local_momentum,
                             weight_decay=opt.weight_decay, nesterov=ls.nesterov,
                             wd_mask=wd_mask, grad_clip=opt.grad_clip,
                             use_kernel=use_kernel)
        if telemetry:
            usq = sum(_sumsq(a.astype(jnp.float32) - b.astype(jnp.float32))
                      for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p0)))
            return p, u, loss, metrics, gsq, usq
        return p, u, loss, metrics

    def local_step(state: LocalSGDState, batch, lr_scale=None):
        """batch: pytree with leading (W, B_loc, ...) dims.

        ``lr_scale`` is the controller's runtime LR multiplier
        (PlanDelta.lr_scale — the noise_adaptive batch-cap handoff);
        ``None`` leaves the scheduled lr_at untouched, keeping the
        static trajectory bitwise-identical."""
        lr = lr_at(opt, state.step, global_batch=global_batch)
        if lr_scale is not None:
            lr = lr * jnp.float32(lr_scale)
        rngs = jax.random.split(jax.random.fold_in(state.rng, state.step), W)
        out = jax.vmap(
            lambda pw, uw, bw, rw: _worker_step(pw, uw, bw, rw, lr, state.step)
        )(state.params, state.momentum, batch, rngs)
        if telemetry:
            p, u, loss, metrics, gsq_w, usq_w = out
            new_stats = tstats.accumulate_step(state.stats, gsq_w, usq_w)
        else:
            p, u, loss, metrics = out
            new_stats = state.stats
        metrics = jax.tree.map(lambda x: x.mean(), metrics)
        metrics = {**metrics, "loss": loss.mean(), "lr": lr}
        new = LocalSGDState(params=p, momentum=u, anchor=state.anchor,
                            global_u=state.global_u, ef_memory=state.ef_memory,
                            step=state.step + 1, rng=state.rng,
                            stats=new_stats)
        return new, metrics

    def sync(state: LocalSGDState, *, group: int | None = None,
             compression=None, plan=None, scope=None) -> LocalSGDState:
        """Thin executor of a :class:`~repro.core.syncplan.SyncPlan`.

        The modern call is ``sync(state, plan=plan, scope=...)``; the
        legacy ``group=`` / ``compression=`` kwargs build an equivalent
        per-call plan (see :func:`_plan_for_call` — ``group != W`` is
        deprecated).  The tree path dispatches whole-tree primitives —
        its collectives are still one-per-sub-bucket under GSPMD via
        the flat bus — so it honors the plan's topology/group/modes but
        requires a UNIFORM compressor mode (per-bucket mode tuples are
        a resident-path feature); overrides require the config to have
        allocated anchor/EF state.
        """
        plan, scope_ = _plan_for_call(state, group=group,
                                      compression=compression, plan=plan,
                                      scope=scope, W=W, ls=ls,
                                      anchored=needs_anchor(ls))
        stages = plan.schedule(scope_)
        g = next(s.group for s in stages if s.kind == "collective")
        if scope_ == "global":
            if len(set(plan.modes)) != 1:
                raise ValueError(
                    "the tree sync path supports a single compression mode "
                    "for the whole state (per-bucket tuples are a "
                    "resident-path feature)")
            mode = plan.modes[0]
        else:
            mode = "none"
        record = telemetry and scope_ == "global"
        if not needs_anchor(ls):
            if mode != "none":
                raise ValueError(
                    "compression override needs an anchor: configure "
                    "sync_compression/global_momentum so the state "
                    "allocates one (needs_anchor)")
            if bucket_sync:
                p = bucket_group_mean(state.params, g, bucketable)
            else:
                p = jax.tree.map(lambda x: group_mean(x, g), state.params)
            new_stats = state.stats
            if record:
                # pre-/post-mean pair of the synced quantity, CENTERED
                # on the already-computed mean: x_k = p_k - pbar, so
                # pre = mean_k ||x_k||^2 IS the worker dispersion and
                # post = ||mean x_k||^2 = 0 exactly.  (Dispersion is
                # shift-invariant; centering avoids the catastrophic
                # cancellation of mean||p_k||^2 - ||pbar||^2, whose
                # f32 resolution is far coarser than the dispersion
                # once workers have nearly converged.)
                cent = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                    - b.astype(jnp.float32), state.params, p)
                pre = _tree_sumsq_w(cent).mean()
                post = jnp.float32(0.0)
                new_stats = tstats.record_sync(state.stats, pre_sync_sq=pre,
                                               post_sync_sq=post)
            return LocalSGDState(params=p, momentum=state.momentum,
                                 anchor=None, global_u=None,
                                 ef_memory=None, step=state.step,
                                 rng=state.rng, stats=new_stats)

        assert g == W, "compression / global momentum require flat local SGD"
        if mode == "ef_sign" and state.ef_memory is None:
            raise ValueError("ef_sign override requires the config to "
                             "allocate EF memory (sync_compression='ef_sign')")
        delta = jax.tree.map(lambda a, p: a[None] - p, state.anchor, state.params)
        ef = state.ef_memory
        err = ref = None
        if mode == "sign":
            raw = delta
            delta = comp.sign_compress(delta, use_kernel=use_kernel,
                                       bucketable=bucketable)
            if record:
                err = sum(_sumsq(r.astype(jnp.float32) - c)
                          for r, c in zip(jax.tree.leaves(raw),
                                          jax.tree.leaves(delta)))
                ref = _tree_sumsq_w(raw).sum()
        elif mode == "ef_sign":
            raw = delta
            delta, ef = comp.ef_compress(delta, ef, use_kernel=use_kernel,
                                         bucketable=bucketable)
            if record:
                # EF residual e' = input - output IS the error
                err = _tree_sumsq_w(ef).sum()
                ref = sum(_sumsq(c + e)
                          for c, e in zip(jax.tree.leaves(delta),
                                          jax.tree.leaves(ef)))
        elif record and speculate_compression:
            cs = comp.sign_compress(delta, use_kernel=use_kernel,
                                    bucketable=bucketable)
            err = sum(_sumsq(d.astype(jnp.float32) - c)
                      for d, c in zip(jax.tree.leaves(delta),
                                      jax.tree.leaves(cs)))
            ref = _tree_sumsq_w(delta).sum()
        if mode != "none" and ls.wire_pack:
            # 1-bit wire format. Bucketized: one packed gather per dtype
            # bucket (make_packed_mean_flat; meshless fallback in CPU
            # tests). Per-leaf path kept for sharded leaves / equivalence.
            pm, axes_tree = packed_mean_fn or (None, None)
            if bucket_sync:
                dbar = bucket_packed_mean(delta, bucketable,
                                          flat_fn=packed_mean_flat_fn,
                                          leaf_fn=pm, axes_tree=axes_tree)
            else:
                if pm is None:
                    def pm(d, axis=-1):
                        packed, scale = comp.pack_signs(d, axis=axis)
                        return comp.unpack_signs(packed, scale, d.shape[1:],
                                                 axis=axis).mean(axis=0)
                if axes_tree is None:
                    dbar = jax.tree.map(lambda d: pm(d, -1), delta)
                else:
                    dbar = jax.tree.map(pm, delta, axes_tree)
        elif bucket_sync:
            dbar = bucket_worker_mean(delta, bucketable)
        else:
            dbar = jax.tree.map(lambda d: d.mean(axis=0), delta)

        new_stats = state.stats
        if record:
            pre = _tree_sumsq_w(delta).mean()
            post = sum(_sumsq(d) for d in jax.tree.leaves(dbar))
            kw = {}
            if err is not None:
                kw = dict(comp_err_sq=err[None], comp_ref_sq=ref[None])
            new_stats = tstats.record_sync(state.stats, pre_sync_sq=pre,
                                           post_sync_sq=post, **kw)

        gu = state.global_u
        if ls.global_momentum > 0:
            gu = jax.tree.map(lambda ug, d: ls.global_momentum * ug + d, gu, dbar)
            step_tree = gu
        else:
            step_tree = dbar
        anchor = jax.tree.map(lambda a, d: (a.astype(jnp.float32)
                                            - d.astype(jnp.float32)).astype(a.dtype),
                              state.anchor, step_tree)
        p = stack_tree(anchor, W)
        return LocalSGDState(params=p, momentum=state.momentum, anchor=anchor,
                             global_u=gu, ef_memory=ef, step=state.step,
                             rng=state.rng, stats=new_stats)

    return init, local_step, sync


# ---------------------------------------------------------------------------
# Resident bucket state: params/momentum/anchor live as flatbuf buckets
# across local steps; the pytree view exists only at unpack_state /
# pack_state boundaries.
# ---------------------------------------------------------------------------

def _bucket_noise(layout, gbs, rng, *, step, eta: float, gamma: float):
    """Isotropic gradient noise straight on grad buckets.

    Same sigma_t = sqrt(eta/(1+t)^gamma) schedule as
    ``noise.isotropic_noise`` but keyed per BUCKET instead of per leaf
    — a different random stream drawing from the same N(0, sigma_t^2)
    distribution.  Consequence (documented contract, ROADMAP):
    noise_eta > 0 trajectories are STATISTICALLY comparable across the
    tree and resident paths (same schedule, same per-element moments —
    pinned by tests/test_noise_parity.py) but NOT bitwise comparable;
    the bitwise trajectory-equivalence harness only covers
    noise_eta == 0.  Noise is masked so padding slots stay exactly zero
    (valid_mask invariant).
    """
    if eta <= 0:
        return gbs
    sigma = jnp.sqrt(eta / (1.0 + step) ** gamma)
    keys = jax.random.split(rng, len(gbs))
    out = []
    for b, (g, k) in enumerate(zip(gbs, keys)):
        n = flatbuf.mask_padding(layout, b,
                                 jax.random.normal(k, g.shape, jnp.float32))
        out.append(g + (sigma * n).astype(g.dtype))
    return out


def _make_resident_local_sgd(run: RunConfig, loss_fn: Callable, *,
                             num_workers: int, wd_mask=None,
                             packed_mean_flat_fn: Callable | None = None,
                             packed_mean_coalesced_fn: Callable | None = None,
                             shard_classes=None,
                             sharded: bool = False, telemetry: bool = False,
                             speculate_compression: bool = False):
    """(init, local_step, sync) with state held resident in bucket form.

    Local steps differentiate the loss THROUGH the bucket view:
    ``flatbuf.view`` is part of the forward graph and its gradient
    writes the leaf grads into grad buckets, so the fused optimizer update
    (``apply_sgd_buckets`` / ``apply_lars_buckets``) performs zero
    explicit flatten/unflatten — the pack cost of the flat bus is paid
    once per sync round (O(1/H)) instead of once per step.  Sync
    consumes and produces buckets directly as well (one collective /
    compressor launch per dtype bucket, no unflatten/re-flatten pair
    between the compressor and the wire pack).

    With ``telemetry``, the per-step grad/update norms come out of the
    SAME fused optimizer launches (``stats=True`` aux outputs in
    kernels/fused_bucket) — zero extra full-state HBM passes and zero
    pack/unpack eqns per step (op-census-tested) — and each global sync
    records the pre-/post-mean norm pair plus per-bucket compression
    error into ``state.stats``.  ``sync`` accepts a per-bucket
    ``compression`` mode tuple (the controller's escalation hook).
    """
    ls = run.local_sgd
    opt = run.optim
    W = num_workers
    global_batch = run.shape.global_batch
    # kernel dispatch: Pallas launches when the state is replicated
    # (meshless CPU/single-host), the GSPMD-friendly jnp forms for both
    # the optimizer and the compressor when the buckets are sharded
    # under a mesh (worker dim, and the row dim of sharded sub-buckets)
    # — a pallas_call on a sharded operand would force a dense gather
    comp_kernel = not sharded

    def init(rng, params_single) -> LocalSGDState:
        layout = flatbuf.build_layout(params_single, wd_mask=wd_mask,
                                      shard_classes=shard_classes)
        pb = flatbuf.flatten(layout, params_single)
        stacked = lambda bufs: tuple(
            jnp.broadcast_to(b[None], (W,) + b.shape) for b in bufs)
        zeros_st = lambda: tuple(jnp.zeros((W,) + b.shape, b.dtype) for b in pb)
        return LocalSGDState(
            params=flatbuf.BucketState(layout, stacked(pb), leading=1),
            momentum=flatbuf.BucketState(layout, zeros_st(), leading=1),
            anchor=(flatbuf.BucketState(layout, tuple(jnp.copy(b) for b in pb))
                    if needs_anchor(ls) else None),
            global_u=(flatbuf.BucketState(layout,
                                          tuple(jnp.zeros_like(b) for b in pb))
                      if ls.global_momentum > 0 else None),
            ef_memory=(flatbuf.BucketState(layout, zeros_st(), leading=1)
                       if ls.sync_compression == "ef_sign" else None),
            step=jnp.int32(0),
            rng=rng,
            stats=(tstats.init_stats(W, layout.num_buckets) if telemetry
                   else None),
        )

    def local_step(state: LocalSGDState, batch, lr_scale=None):
        """batch: pytree with leading (W, B_loc, ...) dims.

        ``lr_scale``: runtime LR multiplier (see the tree-path
        ``local_step``); ``None`` keeps the scheduled lr bitwise."""
        lr = lr_at(opt, state.step, global_batch=global_batch)
        if lr_scale is not None:
            lr = lr * jnp.float32(lr_scale)
        rngs = jax.random.split(jax.random.fold_in(state.rng, state.step), W)
        layout = state.params.layout
        step_no = state.step

        def loss_all(bufs):
            # the pytree view materialized here is the model's activation
            # input; its AD transpose writes the leaf grads straight into
            # grad buckets (flatbuf.view, taken outside the worker vmap).
            # Workers are independent, so the gradient of the summed
            # loss is each worker's own gradient.
            tree = flatbuf.view(layout, bufs, leading=1)
            loss, metrics = jax.vmap(loss_fn)(tree, batch)
            return loss.sum(), (loss, metrics)

        (_, (loss, metrics)), gbs_all = jax.value_and_grad(
            loss_all, has_aux=True)(tuple(state.params.buckets))

        def update_w(pbs, gbs, ubs, rw):
            gbs = list(gbs)
            if opt.noise_eta > 0:
                gbs = _bucket_noise(layout, gbs, rw, step=step_no,
                                    eta=opt.noise_eta, gamma=opt.noise_gamma)
            if opt.optimizer == "lars":
                out = apply_lars_buckets(
                    layout, list(pbs), gbs, list(ubs), lr=lr,
                    trust=opt.lars_trust, momentum_coef=ls.local_momentum,
                    weight_decay=opt.weight_decay, nesterov=ls.nesterov,
                    want_stats=telemetry, kernel=comp_kernel)
            else:
                out = apply_sgd_buckets(
                    layout, list(pbs), gbs, list(ubs), lr=lr,
                    momentum_coef=ls.local_momentum,
                    weight_decay=opt.weight_decay, nesterov=ls.nesterov,
                    grad_clip=opt.grad_clip, want_stats=telemetry,
                    kernel=comp_kernel)
            if telemetry:
                p2, u2, (gsq, usq) = out
                return tuple(p2), tuple(u2), gsq, usq
            p2, u2 = out
            return tuple(p2), tuple(u2)

        out = jax.vmap(update_w)(state.params.buckets, gbs_all,
                                 state.momentum.buckets, rngs)
        if telemetry:
            p, u, gsq_w, usq_w = out
            new_stats = tstats.accumulate_step(state.stats, gsq_w, usq_w)
        else:
            p, u = out
            new_stats = state.stats
        metrics = jax.tree.map(lambda x: x.mean(), metrics)
        metrics = {**metrics, "loss": loss.mean(), "lr": lr}
        new = LocalSGDState(params=state.params.with_buckets(p),
                            momentum=state.momentum.with_buckets(u),
                            anchor=state.anchor, global_u=state.global_u,
                            ef_memory=state.ef_memory, step=state.step + 1,
                            rng=state.rng, stats=new_stats)
        return new, metrics

    def sync(state: LocalSGDState, *, group: int | None = None,
             compression=None, plan=None, scope=None) -> LocalSGDState:
        """Staged executor of a :class:`~repro.core.syncplan.SyncPlan`,
        entirely in bucket form.

        The modern call is ``sync(state, plan=plan, scope=...)``; the
        legacy ``group=`` / ``compression=`` kwargs build an equivalent
        per-call plan (:func:`_plan_for_call` — ``group != W`` is
        deprecated).  Stages run in the plan's declared order —
        ``pack -> collective -> apply`` per sub-bucket, pipelined under
        the ``overlap`` topology, with ``coalesced=True`` collective
        stages sharing one payload gather per dtype — and every
        ordering is a topological order of the same pure per-bucket
        dataflow, so the trajectory is bitwise-identical across
        topologies.  Per-bucket mode tuples (the ``auto_compress``
        controller's none -> sign -> ef_sign escalation) arrive either
        as the legacy ``compression=`` tuple or rewritten into the plan
        via ``plan.with_modes`` / ``PlanDelta``.
        """
        plan, scope_ = _plan_for_call(state, group=group,
                                      compression=compression, plan=plan,
                                      scope=scope, W=W, ls=ls,
                                      anchored=needs_anchor(ls))
        layout = state.params.layout
        nb = layout.num_buckets
        pb = list(state.params.buckets)
        stages = plan.schedule(scope_)
        record = telemetry and scope_ == "global"
        modes = plan.modes if scope_ == "global" else ("none",) * nb
        if not needs_anchor(ls):
            if any(m != "none" for m in modes):
                raise ValueError(
                    "compression override needs an anchor: configure "
                    "sync_compression/global_momentum so the state "
                    "allocates one (needs_anchor)")
            p: list = [None] * nb
            for st in stages:
                if st.kind != "collective":
                    continue
                for b in st.buckets:
                    p[b] = group_mean(pb[b], st.group)
            new_stats = state.stats
            if record:
                # centered pre-/post-mean pair (see the tree-path sync):
                # x_k = p_k - pbar, pre = dispersion, post = 0 exactly —
                # immune to the cancellation of mean||p_k||^2 - ||pbar||^2
                pre = sum(_sumsq(b.astype(jnp.float32)
                                 - m.astype(jnp.float32), from_axis=1)
                          for b, m in zip(pb, p)).mean()
                post = jnp.float32(0.0)
                new_stats = tstats.record_sync(state.stats, pre_sync_sq=pre,
                                               post_sync_sq=post)
            return LocalSGDState(params=state.params.with_buckets(p),
                                 momentum=state.momentum, anchor=None,
                                 global_u=None, ef_memory=None,
                                 step=state.step, rng=state.rng,
                                 stats=new_stats)

        assert scope_ == "global", \
            "compression / global momentum require flat local SGD"
        if "ef_sign" in modes and state.ef_memory is None:
            raise ValueError("ef_sign override requires the config to "
                             "allocate EF memory (sync_compression='ef_sign')")
        ab = list(state.anchor.buckets)
        # strict: every field must share the params bucket structure
        # (pack_state preserves it even for dtype-promoted ef/global_u)
        delta = [a[None] - p for a, p in zip(ab, pb, strict=True)]
        ef = state.ef_memory
        efb = list(ef.buckets) if ef is not None else None
        flat_fn = packed_mean_flat_fn or _packed_mean_flat_local
        coal_fn = packed_mean_coalesced_fn or _packed_mean_coalesced_local
        x = list(delta)                 # the synced quantity per bucket
        dbar: list = [None] * nb
        gub: list = [None] * nb
        anchor_b: list = [None] * nb
        err = [jnp.float32(0.0)] * nb
        ref = [jnp.float32(0.0)] * nb
        for st in stages:
            if st.kind == "pack":
                b = st.buckets[0]       # pack stages carry one sub-bucket
                d = delta[b]
                if modes[b] != "none":
                    x[b], e_new, inp = comp.compress_stage(
                        layout, st, d, efb[b] if efb is not None else None,
                        leading=1, kernel=comp_kernel)
                    if modes[b] == "ef_sign":
                        efb[b] = e_new
                    if record:
                        # the compressor residual input - output (for EF
                        # this IS the new memory e')
                        err[b] = _sumsq(inp.astype(jnp.float32) - x[b])
                        ref[b] = _sumsq(inp)
                elif record and speculate_compression:
                    # measure the WOULD-BE sign error so auto_compress
                    # can decide when to start compressing this bucket
                    cs = comp.sign_compress_bucket(layout, b, d, leading=1,
                                                   kernel=comp_kernel)
                    err[b] = _sumsq(d.astype(jnp.float32) - cs)
                    ref[b] = _sumsq(d)
            elif st.kind == "collective":
                wire = [b for b in st.buckets
                        if modes[b] != "none" and ls.wire_pack]
                if st.coalesced and len(wire) == len(st.buckets) > 1:
                    outs = coal_fn([x[b] for b in st.buckets], layout,
                                   st.buckets)
                    for b, db in zip(st.buckets, outs, strict=True):
                        # the 1-bit unpack emits sign(+1)*scale in padding
                        # slots; re-mask so padding-is-zero survives
                        dbar[b] = flatbuf.mask_padding(layout, b, db)
                    continue
                for b in st.buckets:
                    if modes[b] != "none" and ls.wire_pack:
                        db = flat_fn(x[b], layout, b)
                        dbar[b] = flatbuf.mask_padding(layout, b, db)
                    else:
                        dbar[b] = x[b].mean(axis=0)
            elif st.kind == "apply":
                for b in st.buckets:
                    if ls.global_momentum > 0:
                        gub[b] = (ls.global_momentum * state.global_u.buckets[b]
                                  + dbar[b])
                        step_b = gub[b]
                    else:
                        step_b = dbar[b]
                    anchor_b[b] = (ab[b].astype(jnp.float32)
                                   - step_b.astype(jnp.float32)
                                   ).astype(ab[b].dtype)
        if ef is not None:
            ef = ef.with_buckets(efb)

        new_stats = state.stats
        if record:
            # accumulated in bucket order AFTER the stage loop, so the
            # float summation order is topology-invariant (and equals
            # the pre-plan executor's in-loop accumulation)
            pre_w = jnp.zeros((W,), jnp.float32)
            for b in range(nb):
                pre_w = pre_w + _sumsq(x[b], from_axis=1)
            pre = pre_w.mean()
            post = sum(_sumsq(d) for d in dbar)
            kw = {}
            if any(m != "none" for m in modes) or speculate_compression:
                kw = dict(comp_err_sq=jnp.stack(err),
                          comp_ref_sq=jnp.stack(ref))
            new_stats = tstats.record_sync(state.stats, pre_sync_sq=pre,
                                           post_sync_sq=post, **kw)

        gu = state.global_u
        if ls.global_momentum > 0:
            gu = gu.with_buckets(gub)
        p = [jnp.broadcast_to(a[None], (W,) + a.shape) for a in anchor_b]
        return LocalSGDState(params=state.params.with_buckets(p),
                             momentum=state.momentum,
                             anchor=state.anchor.with_buckets(anchor_b),
                             global_u=gu, ef_memory=ef, step=state.step,
                             rng=state.rng, stats=new_stats)

    return init, local_step, sync
