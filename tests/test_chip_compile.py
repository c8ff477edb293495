"""Compile-only checks of the main-path bucket kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: the
topology is DESCRIBED (``v5e:2x2``) and each kernel is lowered and
compiled for one of its devices at the width of paper-lm's f32 bucket.
This catches what the interpret-mode tests cannot — Mosaic refuses
block shapes that are not whole (8, 128) tiles, for example — at no
chip time.  Nothing runs, so these tests say nothing about values.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and the test
workers each import every test file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import flatbuf
from repro.kernels import fused_bucket as fb
from repro.models import base as mbase
from repro.models import lm

LANE = fb.LANE


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def rows():
    """Rows of paper-lm's single f32 bucket (934,144 at the published
    widths), from the same layout the resident trainer builds."""
    specs = lm.param_specs(configs.get("paper-lm"))
    lay = flatbuf.build_layout(mbase.abstract(specs, jnp.dtype("float32")),
                               wd_mask=mbase.norm_param_mask(specs))
    assert lay.num_buckets == 1
    return lay.bucket_rows[0]


@pytest.fixture(scope="module")
def tp_bucket():
    """(rows, shards) of paper-lm's bf16 sub-bucket that a two-way
    'model' axis shards (tensor parallel within a worker)."""
    import dataclasses

    from repro.sharding.layout import train_layout
    specs = lm.param_specs(configs.get("paper-lm"))
    lay = dataclasses.replace(
        train_layout(("data", "model"), worker_axes=("data",)),
        sizes={"data": 2, "model": 2})
    blay = flatbuf.build_layout(mbase.abstract(specs, jnp.dtype("bfloat16")),
                                wd_mask=mbase.norm_param_mask(specs),
                                shard_classes=flatbuf.shard_classes(specs, lay))
    b = next(b for b in range(blay.num_buckets) if blay.bucket_class(b))
    return blay.bucket_rows[b], blay.bucket_shard_count(b)


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args, **static):
    """Compile ``fn`` (already jitted) for the described chip with the
    kernel forced to its compiled (non-interpreted) form."""
    text = fn.lower(*args, interpret=False, **static).compile().as_text()
    assert "tpu_custom_call" in text, "kernel was not compiled for the chip"
    return text


def _fused_sgd(one_chip, rows, dtype, stats, shards):
    buf = _arg((rows, LANE), dtype, one_chip)
    _compile(fb.fused_sgd_bucket_2d, buf, buf, buf,
             _arg((1, 1), "float32", one_chip),
             _arg((rows, 1), "float32", one_chip),
             momentum=0.9, weight_decay=1e-4, nesterov=True, stats=stats,
             shards=shards)


@pytest.mark.parametrize("dtype,stats", [
    ("float32", False), ("float32", True), ("bfloat16", True)])
def test_fused_sgd_bucket_compiles(one_chip, rows, dtype, stats):
    _fused_sgd(one_chip, rows, dtype, stats, 1)


@pytest.mark.parametrize("stats", [False, True])
def test_fused_sgd_sharded_bucket_compiles(one_chip, tp_bucket, stats):
    rows, shards = tp_bucket
    _fused_sgd(one_chip, rows, "bfloat16", stats, shards)


def test_sq_sum_compiles(one_chip, rows):
    _compile(fb.sq_sum_2d, _arg((rows, LANE), "float32", one_chip))


def test_row_abs_sum_compiles(one_chip, rows):
    _compile(fb.row_abs_sum_2d, _arg((rows, LANE), "float32", one_chip))


def test_scale_sign_rows_compiles(one_chip, rows):
    _compile(fb.scale_sign_rows_2d, _arg((rows, LANE), "float32", one_chip),
             _arg((rows, 1), "float32", one_chip))


def test_lars_row_norms_compiles(one_chip, rows):
    buf = _arg((rows, LANE), "float32", one_chip)
    _compile(fb.lars_row_norms_2d, buf, buf,
             _arg((rows, 1), "float32", one_chip), weight_decay=1e-4)


def test_fused_lars_bucket_stats_compiles(one_chip, rows):
    buf = _arg((rows, LANE), "float32", one_chip)
    col = _arg((rows, 1), "float32", one_chip)
    _compile(fb.fused_lars_bucket_2d, buf, buf, buf,
             _arg((1, 1), "float32", one_chip), col, col,
             momentum=0.9, weight_decay=1e-4, nesterov=True, stats=True)

