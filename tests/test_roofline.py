"""Roofline machinery: analytic FLOP model vs XLA cost_analysis, HLO
collective parser, banded-area arithmetic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import base as mbase
from repro.models import lm
from repro.roofline.analysis import (banded_area, forward_flops, kv_cache_bytes,
                                     num_params, active_params)
from repro.roofline.hlo import _ring_bytes, _shape_bytes, parse_collectives


def test_banded_area():
    assert banded_area(4, 0) == 10          # causal triangle
    assert banded_area(4, 2) == 3 + 2 * 2   # windowed
    assert banded_area(8, 8) == 36
    assert banded_area(8, 100) == 36        # window >= S => full triangle


def test_shape_bytes_and_ring_costs():
    assert _shape_bytes("f32[4,8]{1,0}") == 128
    assert _shape_bytes("(f32[2], bf16[2,2])") == 16
    assert _ring_bytes("all-reduce", 100, 4) == pytest.approx(150.0)
    assert _ring_bytes("all-gather", 100, 4) == pytest.approx(75.0)
    assert _ring_bytes("all-reduce", 100, 1) == 0.0


def test_parse_collectives_iota_groups():
    hlo = ("%ar = f32[256,64]{1,0} all-reduce(%x), channel_id=1, "
           "replica_groups=[16,4]<=[4,16]T(1,0), use_global_device_ids=true")
    s = parse_collectives(hlo, pod_size=32)
    assert s.count() == 1
    op = s.ops[0]
    assert op.group_size == 4
    assert op.result_bytes == 256 * 64 * 4
    # groups built from the transposed iota: {0,16,32,48} -> spans pods of 32
    assert op.crosses_pod


def test_parse_collectives_tpu_tuple_layouts():
    """A tuple result whose TPU tilings carry parentheses (the two scalar
    metrics of a local step, as the TPU compiler prints them)."""
    hlo = ("%all-reduce.2 = (f32[]{:T(128)}, f32[]{:T(128)}) "
           "all-reduce(%div.265, %convert.55), channel_id=1, "
           "replica_groups=[1,4]<=[4], use_global_device_ids=true\n"
           "%ag = u8[4,3096,16]{1,2,0:T(8,128)(4,1)S(1)} all-gather(%b), "
           "replica_groups={{0,1,2,3}}, dimensions={0}")
    ops = parse_collectives(hlo).ops
    assert [(o.op, o.result_bytes, o.group_size) for o in ops] == [
        ("all-reduce", 8, 4), ("all-gather", 4 * 3096 * 16, 4)]


def test_analytic_flops_vs_cost_analysis():
    """Tiny dense config, fully unrolled + single-block attention/loss so
    cost_analysis sees everything; analytic model within 2x (the unrolled
    single-block attention computes the masked half, analytic counts the
    banded area only)."""
    cfg = configs.get_smoke("phi4-mini-3.8b")
    params = mbase.materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
    B, S = 2, 64
    toks = jnp.zeros((B, S), jnp.int32)

    def fwd(p, t):
        out = lm.forward(cfg, p, t, scan=False, remat="none",
                         block_q=S, block_k=S)
        s, n = lm.chunked_xent(cfg, p, out["hidden"], t, block=S)
        return s / jnp.maximum(n, 1)

    compiled = jax.jit(fwd).lower(params, toks).compile()
    measured = compiled.cost_analysis()["flops"]
    analytic = forward_flops(cfg, B, S)
    ratio = measured / analytic
    assert 0.5 < ratio < 2.0, (measured, analytic)


def test_active_params_moe_discount():
    cfg = configs.get("olmoe-1b-7b")
    n = num_params(cfg)
    a = active_params(cfg)
    assert a < n
    # 64 experts, top-8 -> routed params cut ~8x
    assert a / n < 0.45


def test_kv_cache_bytes_families():
    gem = configs.get("gemma3-1b")
    full = kv_cache_bytes(gem.replace(blocks=(gem.blocks[-1],)), 1, 32768)
    slid = kv_cache_bytes(gem, 1, 32768)
    assert slid < full  # sliding-window layers cap their cache
    x = configs.get("xlstm-1.3b")
    assert kv_cache_bytes(x, 1, 524288) == kv_cache_bytes(x, 1, 1024)  # O(1)
