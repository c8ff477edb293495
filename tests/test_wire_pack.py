"""1-bit wire-pack round-trips + pack-axis selection + compressor-scale
regressions (ISSUE-1 satellites). Plain pytest — runs without hypothesis.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as comp
from repro.core.local_sgd import pack_axes_tree
from repro.kernels import ops, ref
from repro.models.base import ParamSpec
from repro.sharding.layout import MeshLayout


# ---------------------------------------------------------------------------
# pack_signs / unpack_signs round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [1, 3, 7, 8, 9, 16, 33, 130])
@pytest.mark.parametrize("axis", [1, 2, -1])
def test_pack_unpack_roundtrip_odd_lengths(length, axis):
    """unpack(pack(x)) == sign(x) * mean|x| for lengths that are not
    multiples of 8, on every non-worker axis."""
    rng = np.random.default_rng(length * 17 + axis)
    x = jnp.asarray(rng.normal(size=(3, 5, length)), jnp.float32)
    packed, scale = comp.pack_signs(x, axis=axis)
    assert packed.dtype == jnp.uint8
    y = comp.unpack_signs(packed, scale, (5, length), axis=axis)
    want = np.sign(np.asarray(x))
    want[want == 0] = 1.0
    want = want * np.abs(np.asarray(x)).reshape(3, -1).mean(1)[:, None, None]
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-6)


def test_pack_signs_zero_is_plus_one():
    """Documented wire-format deviation: sign(0) packs as +1 (vs 0 in
    sign_compress_leaf) — exact-zero deltas only."""
    x = jnp.zeros((2, 9), jnp.float32).at[0, 3].set(-1.0).at[1, 5].set(2.0)
    packed, scale = comp.pack_signs(x, axis=1)
    y = np.asarray(comp.unpack_signs(packed, scale, (9,), axis=1))
    # zeros decode as +scale, not 0
    np.testing.assert_allclose(y[0][np.arange(9) != 3],
                               np.full(8, float(scale[0])), rtol=1e-6)
    np.testing.assert_allclose(y[0][3], -float(scale[0]), rtol=1e-6)


def test_pack_wire_bytes_are_8x_smaller():
    x = jnp.ones((4, 64, 16), jnp.float32)
    packed, scale = comp.pack_signs(x, axis=-1)
    dense = x.size * 4
    wire = packed.size * 1 + scale.size * 4
    assert dense / wire > 7.5  # 1 bit per element + one f32 scale per worker


# ---------------------------------------------------------------------------
# pack-axis selection never picks a sharded dim
# ---------------------------------------------------------------------------

def _layout(sizes):
    return MeshLayout(mesh_axes=("data", "model"), worker_axes=("data",),
                      rules={"mlp": "model", "vocab": "model", "embed": None,
                             "heads": "model"},
                      sizes=sizes)


def test_pack_axes_tree_never_selects_sharded_dim():
    lay = _layout({"data": 4, "model": 4})
    specs = {
        "ffn": ParamSpec((256, 512), ("embed", "mlp")),     # mlp sharded
        "head": ParamSpec((512, 256), ("vocab", "embed")),  # vocab sharded
        "norm": ParamSpec((256,), ("embed",)),              # unsharded
    }
    axes = pack_axes_tree(specs, lay)
    # +1 offsets for the leading worker dim of the stacked leaf
    assert axes["ffn"] == 1     # embed dim, NOT the sharded mlp dim (2)
    assert axes["head"] == 2    # embed dim, NOT the sharded vocab dim (1)
    assert axes["norm"] == 1
    for k, s in specs.items():
        ax = axes[k]
        if ax >= 1:
            logical = s.axes[ax - 1]
            rule = lay.rule(logical) if logical else None
            sharded = rule is not None and lay.axis_size(rule) > 1 and \
                s.shape[ax - 1] % lay.axis_size(rule) == 0
            assert not sharded, (k, ax)


def test_pack_axes_tree_uses_effective_rules():
    """Both dims name 'model', but first-wins dedup means the spec only
    shards dim0 — dim1 is ACTUALLY unsharded and is the right pack axis
    (the old divisibility-only logic wrongly fell back to -1)."""
    lay = _layout({"data": 4, "model": 4})
    specs = {"w": ParamSpec((512, 512), ("mlp", "vocab"))}
    assert tuple(lay.spec("mlp", "vocab", dims=(512, 512))) == ("model", None)
    assert pack_axes_tree(specs, lay)["w"] == 2   # +1 for the worker dim


def test_pack_axes_tree_fallback_when_truly_all_sharded():
    """A genuinely fully-sharded leaf (distinct mesh axes per dim, no
    dedup relief) falls back to -1 (last dim)."""
    lay = MeshLayout(mesh_axes=("data", "model"), worker_axes=(),
                     rules={"mlp": "model", "vocab": "data"},
                     sizes={"data": 4, "model": 4})
    specs = {"w": ParamSpec((512, 512), ("mlp", "vocab"))}
    assert pack_axes_tree(specs, lay)["w"] == -1


def test_shard_classes_follow_effective_spec():
    """Sub-bucket classification == the effective PartitionSpec rules
    (replaces the retired bucketable_tree)."""
    from repro.core import flatbuf
    lay = _layout({"data": 4, "model": 4})
    specs = {
        "ffn": ParamSpec((256, 512), ("embed", "mlp")),
        "norm": ParamSpec((256,), ("embed",)),
        "odd": ParamSpec((256, 510), ("embed", "mlp")),  # 510 % 4 != 0: dropped rule
    }
    cls = flatbuf.shard_classes(specs, lay)
    assert cls["ffn"] == flatbuf.ShardClass(axes=("model",), dims=((1, 4),))
    assert cls["norm"] == flatbuf.REPLICATED
    assert cls["odd"] == flatbuf.REPLICATED  # shape-aware drop => replicated
    rep = flatbuf.replicated_tree(cls)
    assert rep == {"ffn": False, "norm": True, "odd": True}


def test_with_mesh_binds_mesh_and_drops_absent_axes():
    """A layout bound to a mesh without a tensor-parallel axis replicates
    its 'model' rules (instead of naming an axis the mesh lacks), carries
    the mesh for its sharding constraints, and refuses a mesh missing a
    worker axis with a message naming it."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.sharding.layout import train_layout
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    lay = train_layout(("data", "model"), worker_axes=("data",)).with_mesh(mesh)
    assert lay.mesh is mesh and lay.sizes == {"data": 1}
    assert lay.rule("heads") is None and lay.rule("mlp") is None
    assert lay.spec("embed", "mlp") == P(None, None)
    assert lay.sharding("embed", "mlp") == NamedSharding(mesh, P(None, None))
    assert MeshLayout(("data",), ("data",)).sharding("embed") == P(None)
    with pytest.raises(ValueError, match="worker axes \\['pod'\\]"):
        train_layout(("pod", "data"), worker_axes=("pod",)).with_mesh(mesh)


def test_shard_classes_uneven_tp_dim_matches_placement():
    """Divisibility-leak regression (ISSUE 4): a leaf whose TP dim does
    not divide the mesh axis must land in the class its PartitionSpec
    actually gets — for EVERY dim, including later divisible ones the
    old divisibility-only test conflated.  Classification and placement
    must agree or the bus forces a GSPMD gather."""
    from repro.core import flatbuf
    lay = _layout({"data": 4, "model": 4})
    # dim0 uneven over model (dropped by the spec), dim1 divisible: the
    # spec shards dim1 — classification must say exactly that, not
    # "replicated" (old leak: flattened into a replicated bucket while
    # placed sharded) nor "sharded on dim0"
    specs = {"w": ParamSpec((510, 512), ("mlp", "vocab"))}
    eff = lay.spec("mlp", "vocab", dims=(510, 512))
    assert tuple(eff) == (None, "model")
    cls = flatbuf.shard_classes(specs, lay)
    assert cls["w"] == flatbuf.ShardClass(axes=("model",), dims=((1, 4),))
    # fully-uneven leaf: spec replicates every dim -> replicated class
    specs2 = {"w": ParamSpec((510, 509), ("mlp", "vocab"))}
    assert flatbuf.shard_classes(specs2, lay)["w"] == flatbuf.REPLICATED
    # first-wins dedup: both dims name 'model'; the spec shards dim0
    # only, so must the class
    specs3 = {"w": ParamSpec((512, 512), ("mlp", "vocab"))}
    cls3 = flatbuf.shard_classes(specs3, lay)
    assert cls3["w"] == flatbuf.ShardClass(axes=("model",), dims=((0, 4),))
    assert tuple(lay.spec("mlp", "vocab", dims=(512, 512))) == ("model", None)


# ---------------------------------------------------------------------------
# Compressor scale regressions (padding + partial grid blocks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [130, 33000])
def test_sign_compress_scale_unbiased_by_padding(n):
    """n=130: lane padding (126 zeros) must not bias the L1 scale.
    n=33000: 258 rows > BLOCK_ROWS exercises the masked partial grid
    block of the abs-sum reduction (previously folded in garbage)."""
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=n), jnp.float32)
    y = np.asarray(ops.sign_compress(x))
    want = np.asarray(ref.sign_compress_ref(x))
    assert np.isfinite(y).all()
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # the single magnitude equals mean|x| over the TRUE element count
    np.testing.assert_allclose(np.unique(np.abs(y[y != 0])),
                               [np.abs(np.asarray(x)).mean()], rtol=1e-5)


@pytest.mark.parametrize("rows", [8, 250, 258, 512, 520])
def test_bucket_reductions_partial_block(rows):
    """sq_sum / row_abs_sum stay exact when rows is not a multiple of
    BLOCK_ROWS (the masked-partial-block case)."""
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, 128)), jnp.float32)
    np.testing.assert_allclose(float(ops.bucket_sq_sum(x)),
                               float(jnp.sum(x * x)), rtol=1e-5)
    from repro.kernels.fused_bucket import row_abs_sum_2d
    np.testing.assert_allclose(np.asarray(row_abs_sum_2d(x))[:, 0],
                               np.abs(np.asarray(x)).sum(1), rtol=1e-5)
