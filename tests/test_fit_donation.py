"""``fit`` donates its state into the step and sync programs and reads
each step's metrics one step late; nothing else donates.

* Bitwise: ``fit`` through ``build_train``'s donating twins gives the
  history, summary and final state of the loop without donation whose
  every step's metrics are read at once (tree and resident paths, H=1
  and H=4, a runtime ``lr_scale``, eval / checkpoint hooks, one elastic
  resize), and its hooks see the state of their own step.
* Donation happens only in ``fit``: the twins' lowered text aliases the
  state's inputs to outputs, the public ``bundle.local_step`` /
  ``bundle.sync`` leave their input readable, and a hand-made bundle
  (no twins) still runs through ``fit``.
* The ``queued`` attribute on ``fit``'s dispatch spans and one
  ``readback`` span per step.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.backend import WorkerSet
from repro.backend.local import LocalBackend
from repro.configs.base import (ControllerConfig, InputShape, LocalSGDConfig,
                                OptimConfig, RunConfig)
from repro.core.controller import ElasticController, StaticController
from repro.data.partition import ShardedBatches
from repro.data.synthetic import lm_examples, markov_lm
from repro.launch import steps as steps_mod
from repro.launch.steps import TrainBundle
from repro.launch.train import fit
from repro.telemetry import Tracer

SEQ, W, B_LOC, STEPS = 16, 4, 2, 8
DATA = lm_examples(markov_lm(vocab=64, num_seqs=128, seq_len=SEQ, seed=0))


def make_run(H, *, controller="static"):
    cfg = configs.get_smoke("paper-lm").replace(vocab_size=64)
    shape = InputShape("t", SEQ, W * B_LOC, "train")
    return RunConfig(
        model=cfg, shape=shape,
        local_sgd=LocalSGDConfig(local_steps=H),
        optim=OptimConfig(base_lr=0.3, base_batch=shape.global_batch,
                          lr_warmup_steps=2, lr_decay_steps=(STEPS // 2,)),
        controller=ControllerConfig(kind=controller), steps=STEPS)


class HalfLR(StaticController):
    """The static schedule with a runtime LR multiplier of 0.5 from the
    first round on."""

    def lr_scale(self) -> float:
        return 0.5


def immediate_reads(bundle, reads):
    """``bundle`` run as the loop ran before donation: its public,
    non-donating programs (wrapped, so they carry no twin), every step's
    metrics read to the host as soon as the step is dispatched."""
    step, sync = bundle.local_step, bundle.sync

    def local_step(s, b, *lr):
        s, m = step(s, b, *lr)
        reads.append({k: float(v) for k, v in m.items()})
        return s, m
    bundle.local_step = local_step
    bundle.sync = lambda s, **kw: sync(s, **kw)
    return bundle


def leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def assert_trees_equal(a, b):
    la, lb = leaves(a), leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


CASES = {
    "tree-h4": dict(H=4),
    "tree-h1": dict(H=1),
    "resident-h4": dict(H=4, use_kernel=True),
    "resident-h1": dict(H=1, use_kernel=True),
    "tree-lr_scale": dict(H=2, controller=HalfLR),
    "tree-hooks": dict(H=2, hooks=True),
    "resident-hooks": dict(H=2, use_kernel=True, hooks=True),
    "tree-resize": dict(H=2, resize=True),
    "resident-resize": dict(H=2, use_kernel=True, resize=True),
}


def run_fit(case, *, reads=None):
    """One ``fit`` of ``case``: through the twins, or (``reads`` given)
    through the immediate-read, non-donating loop."""
    uk = case.get("use_kernel", False)
    run = make_run(case["H"], controller="elastic" if case.get("resize")
                   else "static")
    if reads is None:
        be = LocalBackend(W, use_kernel=uk)
    else:
        be = LocalBackend(W, build_fn=lambda r, ws: immediate_reads(
            steps_mod.build_train(r, worker_set=ws or WorkerSet.of(W),
                                  use_kernel=uk), reads))
    kw = {}
    if case.get("resize"):
        kw["controller"] = ElasticController(run, resize_at={1: W // 2})
    elif "controller" in case:
        kw["controller"] = case["controller"](run)
    seen = {"eval": [], "checkpoint": []}
    if case.get("hooks"):
        def eval_fn(state):
            seen["eval"].append(leaves(state))
            return {"p": float(jax.tree.leaves(state.params)[0].sum())}

        def checkpoint_fn(state, t):
            seen["checkpoint"].append((t, leaves(state)))
        kw.update(eval_every=3, eval_fn=eval_fn, checkpoint_every=2,
                  checkpoint_fn=checkpoint_fn)
    state, hist, summary = fit(run, ShardedBatches(DATA, W, B_LOC, seed=0),
                               backend=be, num_steps=STEPS, seed=0,
                               log=lambda *a: None, **kw)
    summary.pop("wall_s")
    return state, hist, summary, seen


@pytest.mark.parametrize("name", list(CASES))
def test_donated_deferred_fit_is_bitwise_the_immediate_loop(name):
    case = CASES[name]
    reads = []
    ref_state, ref_hist, ref_summary, ref_seen = run_fit(case, reads=reads)
    state, hist, summary, seen = run_fit(case)

    assert_trees_equal(state, ref_state)
    assert hist == ref_hist and summary == ref_summary
    # each record holds its own step's metrics, as read at once
    assert [h["step"] for h in hist] == list(range(STEPS))
    assert [{k: h[k] for k in r} for h, r in zip(hist, reads)] == reads
    assert len(reads) == STEPS
    H = case["H"]
    assert [h["synced"] for h in hist] == [
        "global" if (t + 1) % H == 0 else "" for t in range(STEPS)]
    if case.get("hooks"):
        assert [h["step"] for h in hist if "eval_p" in h] == [2, 5]
        assert [t for t, _ in seen["checkpoint"]] == [1, 3, 5, 7]
        for (t, got), (_, want) in zip(seen["checkpoint"],
                                       ref_seen["checkpoint"]):
            assert_trees_equal(got, want)
        for got, want in zip(seen["eval"], ref_seen["eval"]):
            assert_trees_equal(got, want)
    if case.get("resize"):
        assert summary["resizes"] == 1
        assert summary["controller"]["lr_scale"] == 0.5
    if "controller" in case:
        assert summary["controller"]["lr_scale"] == 0.5


@pytest.fixture(scope="module")
def built():
    """A ``build_train`` bundle, an initial state and a batch."""
    run = make_run(2)
    bundle = steps_mod.build_train(run, num_workers=W)
    from repro.models import base as mbase
    p0 = mbase.materialize(bundle.specs, jax.random.PRNGKey(0))
    batch = next(ShardedBatches(DATA, W, B_LOC, seed=0))
    return run, bundle, lambda: bundle.init(jax.random.PRNGKey(1), p0), batch


def test_the_twins_alias_the_state_and_the_public_programs_do_not(built):
    _, bundle, init, batch = built
    state = init()
    n = len(jax.tree.leaves(state))
    plan = bundle.sync_plan
    texts = {
        "step": bundle.local_step.lower(state, batch).as_text(),
        "step_twin": bundle.local_step.donating.lower(state,
                                                      batch).as_text(),
        "sync": bundle.sync.lower(state, plan=plan,
                                  scope="global").as_text(),
        "sync_twin": bundle.sync.donating.lower(
            state, plan=plan, scope="global").as_text(),
    }
    assert bundle.step_lower(state, batch).as_text() == texts["step"]
    for name, text in texts.items():
        aliased = text.count("tf.aliasing_output")
        assert aliased == (n if name.endswith("_twin") else 0), name


def test_the_public_programs_leave_their_input_readable(built):
    _, bundle, init, batch = built
    # no NumPy view is taken before the calls: on the CPU a buffer that
    # NumPy views is copied, not donated
    state = init()
    s1, _ = bundle.local_step(state, batch)
    s2 = bundle.sync(s1, plan=bundle.sync_plan, scope="global")
    for tree in (state, s1):
        assert not any(x.is_deleted() for x in jax.tree.leaves(tree))
    # a second step from the same input gives the same state
    again, _ = bundle.local_step(state, batch)
    assert_trees_equal(again, s1)
    # the twins consume theirs, with the same result
    fresh = init()
    d1, _ = bundle.local_step.donating(fresh, batch)
    assert all(x.is_deleted() for x in jax.tree.leaves(fresh))
    assert_trees_equal(d1, s1)
    d2 = bundle.sync.donating(d1, plan=bundle.sync_plan, scope="global")
    assert all(x.is_deleted() for x in jax.tree.leaves(d1))
    assert_trees_equal(d2, s2)


def test_a_hand_made_bundle_without_twins_runs_through_fit(built):
    run, bundle, _, _ = built
    step, sync = bundle.local_step, bundle.sync
    fields = {f.name: getattr(bundle, f.name)
              for f in dataclasses.fields(TrainBundle)}
    fields.update(local_step=lambda s, b, *lr: step(s, b, *lr),
                  sync=lambda s, **kw: sync(s, **kw), worker_set=None)
    hand = TrainBundle(**fields)
    assert not hasattr(hand.local_step, "donating")
    feed = lambda: ShardedBatches(DATA, W, B_LOC, seed=0)  # noqa: E731
    with pytest.warns(DeprecationWarning):
        s_hand, h_hand, _ = fit(run, feed(), bundle=hand, num_steps=4,
                                log=lambda *a: None)
    s_twin, h_twin, _ = fit(run, feed(), bundle=bundle, num_steps=4,
                            log=lambda *a: None)
    assert h_hand == h_twin
    assert_trees_equal(s_hand, s_twin)


def test_dispatch_spans_say_whether_a_program_was_queued(built):
    run, bundle, _, _ = built
    tracer = Tracer()
    fit(run, ShardedBatches(DATA, W, B_LOC, seed=0), bundle=bundle,
        num_steps=4, tracer=tracer, log=lambda *a: None)
    dispatch = [s for s in tracer.spans if s.name == "step_dispatch"]
    syncs = [s for s in tracer.spans if s.name == "sync"]
    assert len(dispatch) == 4 and len(syncs) == 2
    # nothing was dispatched before the first step of a fit
    assert dispatch[0].attrs["queued"] == 0
    assert {s.attrs["queued"] for s in dispatch + syncs} <= {0, 1}
    reads = [s.attrs["step"] for s in tracer.spans if s.name == "readback"]
    assert reads == [0, 1, 2, 3]


def test_fit_consumes_each_state_it_hands_a_hook(built):
    """The state a hook gets is valid only during the call: ``fit``
    donates it into the next step, so a kept reference reads deleted."""
    run, bundle, _, _ = built
    kept = []
    state, _, _ = fit(run, ShardedBatches(DATA, W, B_LOC, seed=0),
                      bundle=bundle, num_steps=4, log=lambda *a: None,
                      checkpoint_every=1,
                      checkpoint_fn=lambda s, t: kept.append(s))
    assert len(kept) == 4 and kept[-1] is state
    for s in kept[:-1]:
        assert all(x.is_deleted() for x in jax.tree.leaves(s))
    assert not any(x.is_deleted() for x in jax.tree.leaves(state))
