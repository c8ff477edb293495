"""The plain reference against the program, at smoke widths on the CPU."""
from __future__ import annotations

import math

import jax
import numpy as np
import pytest

from smoke import BENCH, cell, config, mix  # first: puts the root on sys.path
from benchmarks.chip import check, reference, traffic, training

WORKLOADS = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("seed", [0, 2**31 + 99])
def test_reference_init_is_the_programs_init_from_the_seed(name, stacked,
                                                           seed):
    from repro.models import base as mbase
    from repro.models import lm
    cfg = config(name, stacked_layers=stacked)
    specs = lm.param_specs(training.model_config(cfg, 32))
    prog = mbase.materialize(specs, jax.random.PRNGKey(seed))
    parts: dict = {}
    for path, x in jax.tree_util.tree_flatten_with_path(prog)[0]:
        name_, layer = training._where(path)
        parts.setdefault(name_, []).append((layer, x))
    ref = reference.make_init(cfg)(jax.random.PRNGKey(seed))
    assert set(parts) == set(ref)
    for k, got in parts.items():
        got.sort(key=lambda part: -1 if part[0] in (None, "all")
                 else part[0])
        x = got[0][1] if len(got) == 1 else np.stack([v for _, v in got])
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(x),
                                      err_msg=k)


@pytest.mark.parametrize("config_name,mix_name", WORKLOADS)
def test_reference_follows_fit_through_a_round(config_name, mix_name):
    """One fit of each cell's first steps (a whole round and its sync) at
    smoke widths: losses, first gradients and parameter changes agree
    with the reference to float32 rounding."""
    cfg = config(config_name)
    tr = mix(mix_name, placement="vmap")
    seed = 2**31 + 7
    tokens = traffic.markov_tokens(vocab=cfg["vocab_size"],
                                   num_seqs=tr["rows"],
                                   seq_len=tr["seq_len"], seed=seed)
    steps = tr["local_steps"] * math.ceil(3 / tr["local_steps"])
    devices = jax.devices()[:1]
    trainer = training.Trainer(cfg, tr, devices, tokens)
    init = reference.make_init(cfg)
    prog = trainer.checked_steps(
        seed, steps, lambda: init(jax.random.PRNGKey(seed)))
    ref = reference.Reference(cfg, tr, devices).train(tokens, seed=seed,
                                                      steps=steps)
    got = check.readings(prog, ref)
    assert got["loss_gap"] < 1e-5, got
    assert got["grad_gap"] < 1e-4, got
    assert got["change_gap"] < 1e-4, got
    assert len(prog["losses"]) == steps


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_decay_of_stacked_norm_scales_is_visible(workload):
    """Weight decay that reaches the per-layer norm scales, as the
    program's stacked-layer path applies it (PERF.md, Open questions),
    fails the cell's limits: a reference that decays them, put in the
    program's place, is refused, while the program on the path the cells
    run (every layer kept apart) is not."""
    c = cell(workload, placement="vmap")
    cfg, tr = c["config"], c["traffic"]
    assert tr["optim"]["weight_decay"] > 0
    seed = 2**31 + 41
    tokens = traffic.markov_tokens(vocab=cfg["vocab_size"],
                                   num_seqs=tr["rows"],
                                   seq_len=tr["seq_len"], seed=seed)
    steps = tr["local_steps"] * math.ceil(3 / tr["local_steps"])
    devices = jax.devices()[:1]
    ref = reference.Reference(cfg, tr, devices).train(tokens, seed=seed,
                                                      steps=steps)
    decays_norms = reference.Reference(cfg, tr, devices)
    decays_norms.sgd = reference.make_sgd(tr["optim"], decays_norms.lr,
                                          no_decay=("final_norm",))
    planted = decays_norms.train(tokens, seed=seed, steps=steps)
    ok, checks = check.judge(check.readings(planted, ref), c["limits"])
    assert not ok, checks
    trainer = training.Trainer(cfg, tr, devices, tokens)
    init = reference.make_init(cfg)
    prog = trainer.checked_steps(seed, steps,
                                 lambda: init(jax.random.PRNGKey(seed)))
    ok, checks = check.judge(check.readings(prog, ref), c["limits"])
    assert ok, checks
