"""CPU checks of ``chip_smoke.py``: its phases at a tiny width (kernels
in interpret mode, the mesh on forced host devices) and its refusal to
report success anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro import configs

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
SMOKE = dict(local_batch=4, seq=32, h=4, rounds=3)


def _tiny():
    return configs.get_smoke("paper-lm").replace(
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=256)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phase_paths_agree_and_learn(chip_smoke):
    lines = []
    res = chip_smoke.one_chip_phase(_tiny(), workers=2, log=lines.append,
                                    **SMOKE)
    steps = SMOKE["h"] * SMOKE["rounds"]
    for name in ("resident", "tree"):
        losses = res[name]["losses"]
        assert len(losses) == steps
        chip_smoke.check_losses(losses)
        assert len(res[name]["step_s"]) == steps
    assert res["max_rel_diff"] <= chip_smoke.LOSS_RTOL
    assert any("resident vs tree" in ln for ln in lines)
    # the checks bite: a diverged or flat run is refused
    with pytest.raises(AssertionError):
        chip_smoke.losses_agree(res["tree"]["losses"],
                                [x * 1.01 for x in res["tree"]["losses"]])
    with pytest.raises(AssertionError):
        chip_smoke.check_losses([5.0, 5.1])
    with pytest.raises(AssertionError):
        chip_smoke.check_losses([5.0, float("nan"), 4.0])


def test_main_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    out = subprocess.run([sys.executable, SCRIPT.name], cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


_FOUR = """
import json, sys
sys.path.insert(0, {root!r})
import jax, chip_smoke
from repro import configs
cfg = configs.get_smoke("paper-lm").replace(
    num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
    d_ff=128, vocab_size=256)
res = chip_smoke.four_chip_phase(cfg, jax.devices()[:4], log=lambda *_: None,
                                 **{smoke!r})
print(json.dumps({{k: {{"diff": v["max_rel_diff"],
                     "sources": v["mesh"]["cost_sources"],
                     "step": v["step_collectives"],
                     "sync": v["sync_collectives"]}}
                  for k, v in res.items()}}))
"""


@pytest.mark.slow
def test_four_chip_phase_on_host_devices():
    """The four-chip phase on four forced host devices: one worker per
    device, no mesh context from the caller, dense and 1-bit sync."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run(
        [sys.executable, "-c", _FOUR.format(root=str(ROOT), smoke=SMOKE)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(res) == {"dense", "sign"}
    for name, r in res.items():
        assert r["sources"] == ["hlo"], name
        assert set(r["step"]) <= {"all-reduce"}, (name, r["step"])
    assert res["dense"]["sync"].get("all-reduce", {}).get("count", 0) >= 1
    assert res["sign"]["sync"]["all-gather"]["count"] == 2
