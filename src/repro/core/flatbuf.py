"""Flat parameter bus: (dtype, sharding-class)-bucketed (rows, 128) views
of a pytree.

Motivation (see ISSUE 1 / Golmant et al. 2018): the per-leaf kernel +
collective dispatch tax grows with the number of parameter tensors, not
with bytes, eroding exactly the fixed-overhead advantage local SGD is
supposed to buy.  This module packs a parameter pytree into a small
number of dtype-homogeneous, contiguous lane-layout buckets so the three
hot paths (optimizer update, sign compressor, sync collective) each run
O(#sub-buckets) dispatches instead of O(#leaves).

Layout invariants
-----------------
* Leaves are visited in ``jax.tree.flatten`` order; a bucket is created
  per distinct (dtype, sharding class) in order of first appearance.
  The sharding class of a leaf (:class:`ShardClass`) is its effective
  within-worker sharding — the ordered tuple of mesh axes that shard its
  dims under a :class:`~repro.sharding.layout.MeshLayout` — derived by
  :func:`shard_classes` from the SAME rule application that builds the
  PartitionSpecs, so classification can never disagree with placement.
* Each leaf is flattened, zero-padded to a multiple of ``LANE`` (128)
  and its row count rounded up to a multiple of ``SUBLANE`` (8), so
  every leaf starts on a (8, 128) f32 tile boundary and the bucket shape
  is always a whole number of TPU tiles.  The padding is paid ONCE per
  flatten, not per kernel call as the old ``ops._to_2d`` path did.
* A SHARDED sub-bucket (class with S = prod(shard factors) > 1) is laid
  out shard-major: every leaf contributes S per-shard blocks of
  ``local_rows`` rows each (its sharded dims split and moved to the
  front before flattening), and the bucket holds shard 0's rows for all
  leaves, then shard 1's, ...  Sharding the bucket's row dim over the
  class's mesh axes therefore gives every device exactly its own slice
  of every leaf — packing a sharded leaf onto the bus is a pure
  relayout, never a gather.  Slot ``row_offset``/``rows`` are
  shard-LOCAL for such buckets; per-row metadata is the local array
  tiled S times, so segmented reductions over the full row space yield
  GLOBAL per-leaf totals (LARS norms, L1 scales) for free.
* Static per-leaf metadata (:class:`LeafSlot`) records bucket id, row
  offset/extent, true element count, original shape, the weight-decay
  mask bit, the sharded dims and the sharding-derived wire-pack axis,
  so masks and segmented reductions are precomputed numpy constants.
* ``flatten``/``unflatten`` support a ``leading`` dim count for stacked
  (W, ...) worker trees: the leading dims ride along untouched and the
  layout is keyed on the per-worker shape.

Padding elements are zero on flatten and dropped on unflatten; every
reduction in this module divides by the TRUE element count, so padded
zeros never bias a scale or a norm.

Resident bucket state
---------------------
:class:`BucketState` wraps the bucket buffers with their (static) layout
as a registered pytree, so optimizer state can live IN bucket form
across local steps (ISSUE 2): ``apply_sgd``/``apply_lars`` kernels and
the sync collectives consume and produce buckets directly, and the
pack cost is paid once per sync round instead of once per step.

Lifecycle contract: while a ``BucketState`` is live, the bucket buffers
are the single source of truth — the pytree view does NOT exist and is
materialized only at explicit boundaries (sync already operates on
buckets; eval/checkpoint/logging call :meth:`BucketState.unpack`).
``BucketState.pack`` re-enters resident form, e.g. after a host-side
``unpack -> mutate -> pack`` round-trip.  All in-bucket arithmetic must
preserve the padding-is-zero invariant (see :func:`valid_mask`): padded
elements start as exact zeros and every resident code path either keeps
them zero (linear updates with zero grads/momentum padding) or re-masks
after an operation that could pollute them (the 1-bit wire unpack).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128
SUBLANE = 8        # f32 sublane; (SUBLANE, LANE) is one TPU tile


@dataclass(frozen=True)
class ShardClass:
    """Effective within-worker sharding of one leaf (static, hashable).

    ``axes``  — mesh axis names sharding the leaf, in dim-major order;
                the empty tuple is the replicated class.
    ``dims``  — (leaf dim index, shard factor) per sharded dim.

    Leaves share a sub-bucket iff they share (dtype, ``axes``, total
    factor): the collapsed shard dim of the bucket is then partitioned
    over the same mesh axes in the same device order for every leaf,
    regardless of WHICH leaf dim each one shards.
    """
    axes: tuple[str, ...] = ()
    dims: tuple[tuple[int, int], ...] = ()

    @property
    def shards(self) -> int:
        return int(np.prod([f for _, f in self.dims])) if self.dims else 1


REPLICATED = ShardClass()


@dataclass(frozen=True)
class LeafSlot:
    """Static metadata for one pytree leaf inside its bucket.

    For a leaf in a SHARDED sub-bucket, ``row_offset``/``rows`` are
    shard-LOCAL (the leaf occupies the same ``[row_offset, row_offset +
    rows)`` slice of every shard's region) while ``size`` stays the
    GLOBAL true element count, so segment totals accumulated over the
    tiled row space divide by the right denominator.
    """
    index: int                 # position in tree-flatten order
    bucket: int                # (dtype, shard-class) bucket id
    seg: int                   # segment id within the bucket (leaf order)
    row_offset: int            # first (shard-local) row of this leaf
    rows: int                  # (shard-local) rows occupied (multiple of SUBLANE)
    size: int                  # true (unpadded) GLOBAL element count
    shape: tuple[int, ...]     # original per-worker shape
    dtype: str                 # numpy dtype name
    skip_wd: bool = False      # True => weight decay is masked off
    pack_axis: int = -1        # sharding-derived wire-pack axis (per-leaf path)
    shard_dims: tuple[tuple[int, int], ...] = ()  # (dim, factor) per sharded dim


@dataclass(frozen=True)
class FlatLayout:
    """Static description of the bucketization of one pytree."""
    treedef: Any
    slots: tuple[LeafSlot, ...]
    bucket_dtypes: tuple[str, ...]
    bucket_rows: tuple[int, ...]           # TOTAL rows (all shards)
    bucket_classes: tuple[tuple[str, ...], ...] = ()   # mesh axes per bucket
    bucket_shards: tuple[int, ...] = ()                # shard count per bucket

    @property
    def num_buckets(self) -> int:
        return len(self.bucket_dtypes)

    @property
    def num_leaves(self) -> int:
        return len(self.slots)

    def bucket_slots(self, b: int) -> list[LeafSlot]:
        return [s for s in self.slots if s.bucket == b]

    def bucket_class(self, b: int) -> tuple[str, ...]:
        """Mesh axes sharding bucket ``b``'s row dim (() = replicated)."""
        return self.bucket_classes[b] if self.bucket_classes else ()

    def bucket_shard_count(self, b: int) -> int:
        return self.bucket_shards[b] if self.bucket_shards else 1

    def bucket_local_rows(self, b: int) -> int:
        """Rows of ONE shard's region (== bucket_rows for replicated)."""
        return self.bucket_rows[b] // self.bucket_shard_count(b)

    def bucket_bytes(self, b: int) -> int:
        return self.bucket_rows[b] * LANE * np.dtype(self.bucket_dtypes[b]).itemsize

    def total_bytes(self) -> int:
        return sum(self.bucket_bytes(b) for b in range(self.num_buckets))


def _leaf_rows(size: int) -> int:
    rows = -(-max(size, 1) // LANE)
    return -(-rows // SUBLANE) * SUBLANE


def build_layout(tree, *, wd_mask=None, pack_axes=None, leading: int = 0,
                 shard_classes=None) -> FlatLayout:
    """Build the static bucket layout for ``tree``.

    ``tree`` leaves may be arrays, tracers or ShapeDtypeStructs (anything
    with ``.shape``/``.dtype``).  ``leading`` strips that many leading
    dims (e.g. 1 for stacked (W, ...) worker trees) before recording the
    per-worker shape.  ``wd_mask``/``pack_axes`` are optional pytrees
    congruent with ``tree`` carrying the skip-weight-decay bit and the
    sharding-derived wire-pack axis per leaf.

    ``shard_classes`` is an optional congruent pytree of
    :class:`ShardClass` (see :func:`shard_classes`): leaves are then
    bucketed per (dtype, class) and sharded classes use the shard-major
    row layout, so FSDP/TP layouts ride the bus without gathers.
    ``None`` puts every leaf in its dtype's replicated bucket (the
    meshless case) — bit-identical to the pre-sub-bucket layout.
    """
    leaves, treedef = jax.tree.flatten(tree)
    n = len(leaves)
    wd = jax.tree.leaves(wd_mask) if wd_mask is not None else [False] * n
    pk = jax.tree.leaves(pack_axes) if pack_axes is not None else [-1] * n
    # is_leaf keeps explicit None entries (= replicated) in the leaf
    # list instead of jax.tree dropping them
    sc = (jax.tree.leaves(shard_classes,
                          is_leaf=lambda x: x is None
                          or isinstance(x, ShardClass))
          if shard_classes is not None else [REPLICATED] * n)
    assert len(wd) == n and len(pk) == n and len(sc) == n, \
        (n, len(wd), len(pk), len(sc))
    keys: list[tuple] = []          # (dtype, class axes, shard count)
    dtypes: list[str] = []
    classes: list[tuple[str, ...]] = []
    shards: list[int] = []
    rows_used: list[int] = []       # shard-LOCAL rows per bucket
    segs: list[int] = []
    slots: list[LeafSlot] = []
    for i, leaf in enumerate(leaves):
        shape = tuple(int(d) for d in leaf.shape[leading:])
        dt = np.dtype(leaf.dtype).name
        c: ShardClass = sc[i] if sc[i] is not None else REPLICATED
        S = c.shards
        key = (dt, c.axes, S)
        if key not in keys:
            keys.append(key)
            dtypes.append(dt)
            classes.append(c.axes)
            shards.append(S)
            rows_used.append(0)
            segs.append(0)
        b = keys.index(key)
        size = int(np.prod(shape)) if shape else 1
        assert size % S == 0, (shape, c)   # guaranteed by effective-spec rules
        rows = _leaf_rows(size // S)       # shard-local rows
        slots.append(LeafSlot(index=i, bucket=b, seg=segs[b],
                              row_offset=rows_used[b], rows=rows, size=size,
                              shape=shape, dtype=dt, skip_wd=bool(wd[i]),
                              pack_axis=int(pk[i]), shard_dims=c.dims))
        rows_used[b] += rows
        segs[b] += 1
    return FlatLayout(treedef=treedef, slots=tuple(slots),
                      bucket_dtypes=tuple(dtypes),
                      bucket_rows=tuple(r * s for r, s in zip(rows_used, shards)),
                      bucket_classes=tuple(classes),
                      bucket_shards=tuple(shards))


# ---------------------------------------------------------------------------
# Flatten / unflatten
# ---------------------------------------------------------------------------

def _to_shard_major(x, shard_dims, leading: int):
    """(*lead, *shape) -> (*lead, S, local_size): split each sharded dim
    into (factor, local) and move the factors to the front in dim order.

    Pure reshape/transpose — under GSPMD this is a relayout of a leaf
    sharded on its dims into the same data sharded on the collapsed
    shard dim, with zero communication.
    """
    lead = x.shape[:leading]
    shape = x.shape[leading:]
    fac = dict(shard_dims)
    new_shape = list(lead)
    factor_pos: list[int] = []
    local_pos: list[int] = []
    for i, d in enumerate(shape):
        f = fac.get(i)
        if f:
            factor_pos.append(len(new_shape))
            new_shape.append(f)
            local_pos.append(len(new_shape))
            new_shape.append(d // f)
        else:
            local_pos.append(len(new_shape))
            new_shape.append(d)
    y = x.reshape(new_shape)
    y = jnp.transpose(y, list(range(leading)) + factor_pos + local_pos)
    return y.reshape(lead + (int(np.prod([f for _, f in shard_dims])), -1))


def _from_shard_major(y, shard_dims, shape, leading: int):
    """Inverse of :func:`_to_shard_major`: (*lead, S, local_size) ->
    (*lead, *shape)."""
    lead = y.shape[:leading]
    fac = dict(shard_dims)
    factors = [f for _, f in sorted(shard_dims)]
    local = tuple(d // fac.get(i, 1) for i, d in enumerate(shape))
    y = y.reshape(lead + tuple(factors) + local)
    k = len(factors)
    perm = list(range(leading))
    fidx = 0
    for i in range(len(shape)):
        if i in fac:
            perm.append(leading + fidx)
            fidx += 1
        perm.append(leading + k + i)
    return jnp.transpose(y, perm).reshape(lead + tuple(shape))


def flatten(layout: FlatLayout, tree, *, leading: int = 0,
            bucket_dtypes: Sequence[str] | None = None) -> list:
    """Pack ``tree`` into one (``*lead``, rows, 128) buffer per bucket.

    Leaves are cast to their bucket dtype (a no-op when the tree matches
    the layout's dtypes, e.g. params/grads/momentum share one layout).
    ``bucket_dtypes`` overrides the target dtype per bucket while
    keeping the layout's GEOMETRY — used to re-pack dtype-promoted state
    (e.g. an EF memory that became f32 after the first sync) into the
    params bucket structure without demoting it.

    Sharded sub-buckets are assembled shard-major: each leaf is first
    relayouted to (*lead, S, local_size) (:func:`_to_shard_major`),
    padded per shard, and the per-shard regions are concatenated along
    the UNSHARDED local axis, so the final (S*local_rows, 128) reshape
    keeps the row dim cleanly partitioned over the class's mesh axes.
    """
    leaves = jax.tree.leaves(tree)
    assert len(leaves) == layout.num_leaves, (len(leaves), layout.num_leaves)
    buckets = []
    for b in range(layout.num_buckets):
        dt = (bucket_dtypes or layout.bucket_dtypes)[b]
        S = layout.bucket_shard_count(b)
        parts = []
        for s in layout.bucket_slots(b):
            x = leaves[s.index].astype(dt)
            lead = x.shape[:leading]
            if S > 1:
                flat = _to_shard_major(x, s.shard_dims, leading)
                pad = s.rows * LANE - s.size // S
                pad_dims = leading + 1
            else:
                flat = x.reshape(lead + (-1,))
                pad = s.rows * LANE - s.size
                pad_dims = leading
            if pad:
                flat = jnp.pad(flat, [(0, 0)] * pad_dims + [(0, pad)])
            parts.append(flat)
        buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
        lead = buf.shape[:leading]
        buckets.append(buf.reshape(lead + (layout.bucket_rows[b], LANE)))
    return buckets


def unflatten(layout: FlatLayout, buckets: Sequence, *, leading: int = 0):
    """Inverse of :func:`flatten`; drops per-leaf padding.

    Leaves keep the dtype of the bucket they come out of, so a bucket
    computed in f32 (e.g. a compressed payload) yields f32 leaves.
    """
    assert len(buckets) == layout.num_buckets
    vals: list = [None] * layout.num_leaves
    for b, buf in enumerate(buckets):
        lead = buf.shape[:leading]
        S = layout.bucket_shard_count(b)
        if S > 1:
            flat = buf.reshape(lead + (S, layout.bucket_local_rows(b) * LANE))
        else:
            flat = buf.reshape(lead + (-1,))
        for s in layout.bucket_slots(b):
            off = s.row_offset * LANE
            if S > 1:
                seg = flat[..., off:off + s.size // S]
                vals[s.index] = _from_shard_major(seg, s.shard_dims, s.shape,
                                                  leading)
            else:
                seg = flat[..., off:off + s.size]
                vals[s.index] = seg.reshape(lead + s.shape)
    return jax.tree.unflatten(layout.treedef, vals)


def _scatter_leaves(layout: FlatLayout, leaves: Sequence, bucket_avals,
                    leading: int):
    """Write each leaf into its slot of one zero bucket per bucket —
    the transpose of :func:`unflatten`, as in-place
    ``dynamic_update_slice`` writes: no per-leaf bucket-sized buffer."""
    out = []
    for b, aval in enumerate(bucket_avals):
        lead = aval.shape[:leading]
        S = layout.bucket_shard_count(b)
        width = layout.bucket_local_rows(b) * LANE
        flat = jnp.zeros(lead + ((S, width) if S > 1 else (width,)),
                         aval.dtype)
        for s in layout.bucket_slots(b):
            x = leaves[s.index].astype(aval.dtype)
            x = (_to_shard_major(x, s.shard_dims, leading) if S > 1
                 else x.reshape(lead + (-1,)))
            start = (0,) * (x.ndim - 1) + (s.row_offset * LANE,)
            flat = jax.lax.dynamic_update_slice(flat, x, start)
        out.append(flat.reshape(aval.shape))
    return out


def view(layout: FlatLayout, buckets: Sequence, *, leading: int = 0):
    """The pytree view of ``buckets``, for differentiating through:
    values as :func:`unflatten`, but the gradient writes each leaf's
    cotangent into its slot of one zero bucket
    (:func:`_scatter_leaves`).  AD through the plain slices would pad
    every leaf's cotangent to a whole bucket and sum the pads — one
    bucket-sized buffer per leaf.  Call it outside any ``vmap`` (pass
    ``leading``): batching turns the slot writes into scatters."""
    avals = [jax.ShapeDtypeStruct(b.shape, b.dtype) for b in buckets]

    @jax.custom_vjp
    def _view(bufs):
        return unflatten(layout, list(bufs), leading=leading)

    def _fwd(bufs):
        return _view(bufs), None

    def _bwd(_, ct):
        return (tuple(_scatter_leaves(layout, jax.tree.leaves(ct), avals,
                                      leading)),)

    _view.defvjp(_fwd, _bwd)
    return _view(tuple(buckets))


# ---------------------------------------------------------------------------
# Resident bucket state
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class BucketState:
    """Bucket buffers + their static layout, as a pytree.

    The buffers are the pytree children (so jit/vmap/sharding see plain
    arrays); ``layout`` and ``leading`` ride as static aux data.  The
    pytree view is materialized ONLY via :meth:`unpack` — between packs
    the buckets are authoritative (see module docstring for the
    lifecycle contract).

    ``leading=1`` marks worker-stacked (W, rows, 128) buffers; the SAME
    layout describes both the stacked and the single-copy form, since
    :func:`build_layout` keys on per-worker shapes.

    Note on dtypes: ``layout.bucket_dtypes`` records the dtype the state
    was PACKED with; resident arithmetic may promote a buffer (e.g. a
    global-momentum bucket becomes f32 after the first sync, exactly as
    the per-leaf reference promotes its leaves) and :meth:`unpack`
    yields leaves in the buffer's actual dtype, mirroring the reference.
    """
    layout: FlatLayout
    buckets: tuple
    leading: int = 0

    def tree_flatten(self):
        return tuple(self.buckets), (self.layout, self.leading)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(layout=aux[0], buckets=tuple(children), leading=aux[1])

    @classmethod
    def pack(cls, tree, *, layout: FlatLayout | None = None, wd_mask=None,
             leading: int = 0) -> "BucketState":
        """Enter resident form: flatten ``tree`` into bucket buffers."""
        if layout is None:
            layout = build_layout(tree, wd_mask=wd_mask, leading=leading)
        return cls(layout=layout,
                   buckets=tuple(flatten(layout, tree, leading=leading)),
                   leading=leading)

    def unpack(self):
        """Materialize the pytree view (the ONLY exit from bucket form)."""
        return unflatten(self.layout, list(self.buckets), leading=self.leading)

    def with_buckets(self, buckets, *, leading: int | None = None) -> "BucketState":
        return BucketState(layout=self.layout, buckets=tuple(buckets),
                           leading=self.leading if leading is None else leading)

    @property
    def num_buckets(self) -> int:
        return self.layout.num_buckets


def is_bucket_state(x) -> bool:
    return isinstance(x, BucketState)


def abstract_buckets(layout: FlatLayout, *, lead: tuple = ()) -> list:
    """ShapeDtypeStruct per bucket buffer: ``(*lead, rows, LANE)``.

    The template form shared by resident checkpoint restores and the
    serving weight-subscriber (a :class:`BucketState` of these SDS
    leaves restores a published bucket snapshot without materializing a
    pytree), and by the serving page pools (``lead=(num_pages,
    page_size)`` turns each bucket into a pool of fixed-size KV pages).
    """
    return [jax.ShapeDtypeStruct(tuple(lead) + (layout.bucket_rows[b], LANE),
                                 jnp.dtype(layout.bucket_dtypes[b]))
            for b in range(layout.num_buckets)]


# ---------------------------------------------------------------------------
# Precomputed per-bucket constants (numpy; static under jit)
# ---------------------------------------------------------------------------

def _tile_shards(layout: FlatLayout, b: int, local: np.ndarray) -> np.ndarray:
    """Tile a shard-local per-row constant over the bucket's S shard
    regions (identity for replicated buckets).  Because every shard's
    region has the same leaf layout, the tiled array is exact — and a
    segmented reduction over ALL rows then accumulates across shards,
    yielding global per-leaf totals."""
    S = layout.bucket_shard_count(b)
    if S == 1:
        return local
    reps = (S,) + (1,) * (local.ndim - 1)
    return np.tile(local, reps)


def wd_rows(layout: FlatLayout, b: int) -> np.ndarray:
    """(rows, 1) f32 mask: 1.0 on rows whose leaf takes weight decay."""
    m = np.zeros((layout.bucket_local_rows(b), 1), np.float32)
    for s in layout.bucket_slots(b):
        if not s.skip_wd:
            m[s.row_offset:s.row_offset + s.rows] = 1.0
    return _tile_shards(layout, b, m)


def row_segments_local(layout: FlatLayout, b: int) -> np.ndarray:
    """(local_rows,) int32: segment id per row of ONE shard's region —
    the in-shard_map form of :func:`row_segments` (every shard's region
    has identical layout)."""
    seg = np.zeros((layout.bucket_local_rows(b),), np.int32)
    for s in layout.bucket_slots(b):
        seg[s.row_offset:s.row_offset + s.rows] = s.seg
    return seg


def row_segments(layout: FlatLayout, b: int) -> np.ndarray:
    """(rows,) int32: bucket-local leaf segment id per row (tiled over
    shard regions for sharded sub-buckets)."""
    return _tile_shards(layout, b, row_segments_local(layout, b))


def segment_sizes(layout: FlatLayout, b: int) -> np.ndarray:
    """(num_segments,) f32: TRUE element count per leaf (excludes padding)."""
    slots = layout.bucket_slots(b)
    out = np.zeros((len(slots),), np.float32)
    for s in slots:
        out[s.seg] = float(s.size)
    return out


def segment_skip_wd(layout: FlatLayout, b: int) -> np.ndarray:
    """(num_segments,) bool: True where the leaf opts out of weight decay
    (norm/bias params — these also take the plain LR under LARS)."""
    slots = layout.bucket_slots(b)
    out = np.zeros((len(slots),), bool)
    for s in slots:
        out[s.seg] = s.skip_wd
    return out


def valid_mask(layout: FlatLayout, b: int) -> np.ndarray:
    """(rows, 128) f32 mask: 1.0 on TRUE elements, 0.0 on padding.

    The dense form, for tests and host-side checks; runtime code uses
    :func:`mask_padding`, which fuses the same mask from the tiny
    per-row valid-lane count instead of baking a bucket-sized constant
    into the executable.
    """
    m = np.zeros((layout.bucket_local_rows(b), LANE), np.float32)
    flat = m.reshape(-1)
    S = layout.bucket_shard_count(b)
    for s in layout.bucket_slots(b):
        off = s.row_offset * LANE
        flat[off:off + s.size // S] = 1.0
    return _tile_shards(layout, b, m)


@functools.lru_cache(maxsize=None)
def lane_counts(layout: FlatLayout, b: int) -> np.ndarray:
    """(rows, 1) int32: number of VALID lanes per row (0 on fully-padded
    rows, 128 mid-leaf, the remainder on a leaf's boundary row).
    Cached per (layout, bucket) — FlatLayout is static and hashable."""
    c = np.zeros((layout.bucket_local_rows(b), 1), np.int32)
    S = layout.bucket_shard_count(b)
    for s in layout.bucket_slots(b):
        c[s.row_offset:s.row_offset + s.rows, 0] = np.clip(
            s.size // S - np.arange(s.rows) * LANE, 0, LANE)
    return _tile_shards(layout, b, c)


def mask_padding(layout: FlatLayout, b: int, x):
    """Zero the padding slots of a (``*lead``, rows, 128) buffer.

    Resident bucket code applies this after any operation that could
    write nonzero values into padding (e.g. the 1-bit wire unpack emits
    sign(+1)*scale everywhere), restoring the padding-is-zero invariant
    that keeps segment norms and L1 scales unbiased.  The mask is a
    lane-iota compare against the (rows, 1) valid-lane count — a
    constant 128x smaller than the bucket that fuses into the consumer
    instead of costing a full extra HBM operand.
    """
    cnt = jnp.asarray(lane_counts(layout, b))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    return x * (lane < cnt).astype(x.dtype)


# ---------------------------------------------------------------------------
# Sharding-derived metadata
# ---------------------------------------------------------------------------

def shard_classes(specs, layout):
    """Per-leaf :class:`ShardClass` pytree from a ParamSpec tree and a
    :class:`~repro.sharding.layout.MeshLayout`.

    Classification goes through ``MeshLayout.dim_shards`` — the EXACT
    rule application (shape-aware divisibility drop + first-wins mesh-
    axis dedup) that ``partition_specs`` uses to place the state — so a
    leaf lands in a sharded sub-bucket iff its PartitionSpec actually
    shards it.  This retires ``bucketable_tree``, whose divisibility-
    only test could disagree with the effective spec (an unevenly
    sharded dim is DROPPED by the spec, hence replicated, hence
    bucketable into the replicated class — never flattened while still
    sharded, which would force a GSPMD gather).
    """
    from repro.models import base as mbase

    def cls(ps: "mbase.ParamSpec") -> ShardClass:
        axes: list[str] = []
        dims: list[tuple[int, int]] = []
        for i, r in enumerate(layout.dim_shards(ps.axes, ps.shape)):
            if r is None:
                continue
            f = layout.axis_size(r)
            if f <= 1:
                continue
            axes.extend((r,) if isinstance(r, str) else r)
            dims.append((i, f))
        return ShardClass(axes=tuple(axes), dims=tuple(dims))

    return jax.tree.map(cls, specs, is_leaf=mbase.is_spec)


def replicated_tree(classes):
    """bool pytree: True where the leaf's class is replicated (the
    per-leaf routing mask of the non-resident tree sync path)."""
    return jax.tree.map(lambda c: c.axes == (), classes,
                        is_leaf=lambda x: isinstance(x, ShardClass))


def bucket_pspec(layout: FlatLayout, b: int, *, worker=None):
    """PartitionSpec of bucket ``b``'s buffer: the row dim is sharded
    over the bucket's class axes (replicated class => fully replicated
    rows); ``worker`` prepends the stacked worker-dim entry."""
    from jax.sharding import PartitionSpec as P

    cls = layout.bucket_class(b)
    row = None if not cls else (cls[0] if len(cls) == 1 else cls)
    if worker is not None:
        return P(worker, row, None)
    return P(row, None)
