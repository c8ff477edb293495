"""The benchmark's FLOP counts and peak table."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import flops, peaks, registry, training  # noqa: E402

BENCH = registry.load_benchmark()
CELLS = [(w["config"], registry.load_traffic(w["traffic"])["seq_len"])
         for w in BENCH["workloads"]]


@pytest.mark.parametrize("config,seq", CELLS)
def test_flops_per_token_match_the_programs_roofline(config, seq):
    from repro.roofline import analysis
    cfg = registry.load_config(BENCH, config)
    mc = training.model_config(cfg, seq)
    want = 3 * analysis.forward_flops(mc, 1, seq) / seq
    assert flops.train_flops_per_token(cfg, seq) == pytest.approx(
        want, rel=1e-12)
    assert flops.forward_flops(cfg, 4, seq) == pytest.approx(
        analysis.forward_flops(mc, 4, seq), rel=1e-12)


@pytest.mark.parametrize("config,seq,gflop", [
    ("smollm2-135m", 128, 0.8203), ("smollm2-135m", 512, 0.8601)])
def test_flops_per_token_orders(config, seq, gflop):
    cfg = registry.load_config(BENCH, config)
    assert flops.train_flops_per_token(cfg, seq) / 1e9 == pytest.approx(
        gflop, rel=1e-3)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_files_state_the_programs_parameter_count(config):
    from repro.roofline import analysis
    cfg = registry.load_config(BENCH, config)
    assert analysis.num_params(training.model_config(cfg, 128)) == \
        cfg["parameters"]


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_an_unknown_device_kind_raises(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak(kind)
