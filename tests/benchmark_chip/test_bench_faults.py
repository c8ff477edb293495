"""``correct`` comes out false when the timed path is broken, and for the
control; true for the program as it is.  Runs the harness's whole
measurement (set-up, window, check) on the CPU at smoke widths, skipping
only its look for a chip."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from smoke import BENCH, cell  # first: puts the root on sys.path
from benchmarks.chip import check, reference, registry, traffic, training
from benchmarks.chip import run as harness

HERE = Path(__file__).resolve().parent
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
# the four-chip cells, or else the first one-chip cell of several workers
# placed one worker a chip
MESH = ([w["name"] for w in BENCH["workloads"] if w["chips"] == 4] or
        [w["name"] for w in BENCH["workloads"]
         if registry.load_traffic(w["traffic"])["workers"] > 1][:1])
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def broken_trainer(fault):
    """The program's Trainer with its timed path broken underneath."""

    class Broken(training.Trainer):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            b = self.bundle
            step = b.local_step
            if fault == "unchanged":
                b.local_step = lambda s, batch, *x: (s, step(s, batch,
                                                             *x)[1])
            elif fault == "half_batch":
                def cut(v):     # half the rows, or half of a single row
                    if v.shape[1] > 1:
                        return v[:, :v.shape[1] // 2]
                    return v[:, :, :v.shape[2] // 2]
                b.local_step = lambda s, batch, *x: step(
                    s, {k: cut(v) for k, v in batch.items()}, *x)
            elif fault == "no_exchange":
                b.sync = lambda s, **kw: s
            elif fault is not None:
                raise ValueError(fault)
    return Broken


def measure(workload, fault, monkeypatch, devices):
    monkeypatch.setattr(training, "Trainer", broken_trainer(fault))
    args = types.SimpleNamespace(seed=2**31 + 21, seconds=0.2, trace=0)
    out = harness.measure(cell(workload), args, devices, PEAK)
    line = harness.result_line(out, devices)
    assert list(line)[-1] == "checks"
    return line


def _faults(workload):
    """The faults a cell can have: no exchange where there is one worker."""
    w = registry.workload(BENCH, workload)
    many = registry.load_traffic(w["traffic"])["workers"] > 1
    return [None, "unchanged", "half_batch"] + (["no_exchange"] if many
                                                else [])


@pytest.mark.parametrize("workload,fault", [(w, f) for w in ONE_CHIP
                                            for f in _faults(w)])
def test_correct_is_false_only_under_a_fault(workload, fault, monkeypatch):
    line = measure(workload, fault, monkeypatch, jax.devices()[:1])
    assert line["correct"] is (fault is None), line["checks"]
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_a_traced_run_reads_its_per_layer_metrics(monkeypatch):
    """The --trace 1 path end to end on the CPU: it profiles, reduces and
    reads; with no TPU planes in the trace the device readers find
    nothing and their metrics are left out of the line."""
    name = ONE_CHIP[-1]
    c = cell(name)
    c["metrics"] = BENCH["per_layer"]
    c["readers"] = {m["name"]: registry.load_reader(m["name"])
                    for m in c["metrics"]}
    args = types.SimpleNamespace(seed=5, seconds=0.2, trace=1)
    devices = jax.devices()[:1]
    line = harness.result_line(harness.measure(c, args, devices, PEAK),
                               devices)
    assert line["correct"] is True, line["checks"]
    assert {"fit_host_ms", "mfu"} <= set(line["metrics"]) <= set(c["readers"])
    assert "local_step_ms" not in line["metrics"]
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_the_control_is_refused(workload):
    """The reference computed in bfloat16, put in the program's place,
    fails the cell's limits."""
    c = cell(workload["name"], placement="vmap")
    cfg, tr = c["config"], c["traffic"]
    seed = 31
    tokens = traffic.markov_tokens(vocab=cfg["vocab_size"],
                                   num_seqs=tr["rows"],
                                   seq_len=tr["seq_len"], seed=seed)
    steps = tr["local_steps"] * math.ceil(3 / tr["local_steps"])
    devices = jax.devices()[:1]
    ref = reference.Reference(cfg, tr, devices).train(tokens, seed=seed,
                                                      steps=steps)
    ctrl = reference.Reference(cfg, tr, devices, dtype=jnp.bfloat16).train(
        tokens, seed=seed, steps=steps)
    ok, checks = check.judge(check.readings(ctrl, ref), c["limits"])
    assert not ok, checks


MESH_SCRIPT = """
import json, sys, types
sys.path[:0] = [{here!r}, {root!r}, {src!r}]
import jax
import test_bench_faults as t
from benchmarks.chip import run as harness, training
from smoke import cell
out = {{}}
for fault in (None, "no_exchange"):
    training.Trainer = t.broken_trainer(fault)
    args = types.SimpleNamespace(seed=2**31 + 23, seconds=0.2, trace=0)
    res = harness.measure(cell({workload!r}, placement="mesh"), args,
                          jax.devices()[:4],
                          t.PEAK)
    out[str(fault)] = [res["correct"], res["checks"]]
print(json.dumps(out))
"""


@pytest.mark.parametrize("workload", MESH)
def test_the_mesh_cell_needs_the_exchange_between_chips(workload):
    """On four host devices, one worker each: the cell is correct as it
    is, and not when the sync's cross-device mean is left out."""
    root = HERE.parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_SCRIPT.format(here=str(HERE), root=str(root),
                              src=str(root / "src"), workload=workload)
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["None"][0] is True, got
    assert got["no_exchange"][0] is False, got
