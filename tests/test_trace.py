"""Trace spine tests (ISSUE 8).

* Tracer/Span basics: timing, attrs, context-manager finish, the
  NullTracer no-op path, fence semantics.
* ``sync_stage_spans`` + ``CommsLedger.record_plan(seconds=)``: the
  attributed per-stage seconds use the SAME stage ids and wire-byte
  weights on both streams, and sum to the measured total.
* MetricsRegistry: Prometheus text exposition (cumulative histogram
  buckets, HELP/TYPE headers), label validation, feeder helpers.
* Exporters: perfetto_trace passes the Chrome schema validator; JSONL
  validator catches missing fields; run manifest carries the
  reproducibility fields.
* fit-level acceptance: a traced smoke fit emits trace + prometheus +
  manifest + extended JSONL, with trace stage ids matching the ledger's
  priced stage rows; and tracing (even fenced) is a pure observer —
  the trajectory is BITWISE identical with it on or off.
* Span tree and driver loop: parent links, self time, a leaf span for
  every stretch of host code in ``fit``'s loop, JAX trace/compile
  counts on the span that paid for them, no listener for the
  NullTracer, and ``clock_offset_ns`` on a real CPU profiler capture.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ControllerConfig, InputShape, LocalSGDConfig,
                                ModelConfig, OptimConfig, RunConfig)
from repro.core import flatbuf
from repro.core import syncplan as splan
from repro.core.controller import StaticController
from repro.core.local_sgd import make_local_sgd
from repro.launch.steps import TrainBundle
from repro.launch.train import fit
from repro.models.base import ParamSpec
from repro.telemetry import CommsLedger
from repro.telemetry import export as texport
from repro.telemetry import metrics as tmetrics
from repro.telemetry import trace as ttrace

W, D, C = 4, 6, 3


# ---------------------------------------------------------------------------
# Tracer / Span basics
# ---------------------------------------------------------------------------

def test_span_lifecycle_and_attrs():
    tr = ttrace.Tracer()
    assert tr.enabled
    with tr.span("round", step=0) as sp:
        sp.set(h=2)
        with tr.span("sync", scope="global") as inner:
            pass
    assert [s.name for s in tr.spans] == ["sync", "round"]  # finish order
    rd = tr.spans[1]
    assert rd.attrs == {"step": 0, "h": 2}
    assert rd.dur_s is not None and rd.dur_s >= 0
    assert rd.cat == "train" and tr.spans[0].cat == "sync"
    # the inner span nests inside the outer's window
    assert rd.ts_s <= inner.ts_s
    assert inner.ts_s + inner.dur_s <= rd.ts_s + rd.dur_s + 1e-6


def test_finish_is_idempotent_and_finish_attrs_land():
    tr = ttrace.Tracer()
    sp = tr.start("eval", step=3)
    tr.finish(sp, extra=1)
    n = len(tr.spans)
    tr.finish(sp)                       # double finish: no second append
    assert len(tr.spans) == n
    assert sp.attrs == {"step": 3, "extra": 1}


def test_null_tracer_is_inert():
    tr = ttrace.NULL
    assert not tr.enabled
    with tr.span("round", step=0) as sp:
        sp.set(h=2)                     # attr dropped, no crash
        out = sp.fence(jnp.ones(3))     # fence still returns the value
    np.testing.assert_array_equal(np.asarray(out), 1.0)
    assert tr.spans == [] and sp.attrs == {}
    assert tr.record("collective", 0.0, 1.0) is ttrace._NULL_SPAN


def test_fence_returns_value_and_blocks_only_when_enabled():
    v = jnp.arange(4.0)
    for fence in (False, True):
        tr = ttrace.Tracer(fence=fence)
        with tr.span("local_steps") as sp:
            assert sp.fence(v) is v


def test_record_appends_premeasured_interval():
    tr = ttrace.Tracer()
    sp = tr.record("collective", 1.0, 0.25, stage=0)
    assert sp.dur_s == 0.25 and sp.ts_s == 1.0
    assert tr.spans == [sp]


# ---------------------------------------------------------------------------
# stage attribution: spans <-> ledger
# ---------------------------------------------------------------------------

def _plan(num_workers=W, compression="sign", **kw):
    lay = flatbuf.build_layout(
        {"w": jax.ShapeDtypeStruct((D, C), jnp.float32),
         "b": jax.ShapeDtypeStruct((C,), jnp.float32)})
    return splan.make_sync_plan(lay, compression=compression,
                                num_workers=num_workers, wire_pack=True,
                                anchored=True, **kw)


def test_sync_stage_spans_apportion_to_parent_total():
    tr = ttrace.Tracer()
    plan = _plan()
    parent = tr.start("sync", scope="global")
    tr.finish(parent)
    parent.dur_s = 0.5                  # pin for exact arithmetic
    stage_s = ttrace.sync_stage_spans(tr, plan, "global", parent)
    stages = plan.collective_stages("global")
    assert [i for i, _ in stage_s] == list(range(len(stages)))
    np.testing.assert_allclose(sum(s for _, s in stage_s), 0.5, rtol=1e-9)
    col = [s for s in tr.spans if s.name == "collective"]
    assert len(col) == len(stages)
    for i, sp in enumerate(col):
        assert sp.attrs["stage"] == i and sp.attrs["attributed"]
        assert sp.attrs["wire_bytes"] == stages[i].wire_bytes
    # contiguous within the parent window
    assert col[0].ts_s == parent.ts_s
    # byte-weighted: a bigger stage gets proportionally more seconds
    wb = [s.wire_bytes for s in stages]
    if max(wb) > min(wb):
        big, small = wb.index(max(wb)), wb.index(min(wb))
        assert stage_s[big][1] > stage_s[small][1]


def test_sync_stage_spans_disabled_or_unfinished():
    plan = _plan()
    assert ttrace.sync_stage_spans(ttrace.NULL, plan, "global",
                                   ttrace._NULL_SPAN) == []
    tr = ttrace.Tracer()
    open_span = tr.start("sync")        # dur_s is None
    assert ttrace.sync_stage_spans(tr, plan, "global", open_span) == []


def test_record_plan_seconds_apportioning_matches_spans():
    """The ledger's stage_s split == the trace's span split: identical
    stage ids, identical byte weights, both summing to the measured
    total — the bytes<->seconds join key of the whole ISSUE."""
    plan = _plan()
    led = CommsLedger()
    out = led.record_plan(step=4, level=2, h=2, plan=plan, seconds=0.8)
    assert out["sync_s"] == pytest.approx(0.8)
    rows = [e for e in led.entries if "stage_s" in e]
    assert [r["stage"] for r in rows] == \
        list(range(len(plan.collective_stages("global"))))
    np.testing.assert_allclose(sum(r["stage_s"] for r in rows), 0.8)
    tr = ttrace.Tracer()
    parent = tr.start("sync")
    tr.finish(parent)
    spans = ttrace.sync_stage_spans(tr, plan, "global", parent, seconds=0.8)
    for (sid, s), row in zip(spans, rows):
        assert sid == row["stage"]
        np.testing.assert_allclose(s, row["stage_s"], rtol=1e-9)
    assert led.summary()["sync_seconds"] == pytest.approx(0.8)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_exposition_format_and_cumulative_buckets():
    reg = tmetrics.MetricsRegistry()
    h = reg.histogram("step_time_seconds", "t", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    reg.counter("rounds_total", "r", labels=("scope",)) \
       .labels(scope="global").inc()
    reg.gauge("h", "h").set(8)
    text = reg.exposition()
    assert "# HELP repro_step_time_seconds t" in text
    assert "# TYPE repro_step_time_seconds histogram" in text
    # cumulative: le=0.1 -> 1, le=1.0 -> 2, +Inf -> 3
    assert 'repro_step_time_seconds_bucket{le="0.1"} 1' in text
    assert 'repro_step_time_seconds_bucket{le="1"} 2' in text
    assert 'repro_step_time_seconds_bucket{le="+Inf"} 3' in text
    assert "repro_step_time_seconds_count 3" in text
    assert 'repro_rounds_total{scope="global"} 1' in text
    assert "repro_h 8" in text


def test_metric_label_and_kind_validation():
    reg = tmetrics.MetricsRegistry()
    m = reg.counter("x_total", labels=("scope",))
    with pytest.raises(ValueError):
        m.labels(nope="a")
    with pytest.raises(ValueError):
        m.labels(scope="g").inc(-1)     # counters only go up
    # idempotent re-register returns the same family ...
    assert reg.counter("x_total", labels=("scope",)) is m
    # ... but a kind/label mismatch is an error, not a silent overwrite
    with pytest.raises(ValueError):
        reg.gauge("x_total")


def test_observe_round_feeds_standard_set():
    reg = tmetrics.MetricsRegistry()
    tmetrics.observe_step(reg, 0.01)
    tmetrics.observe_round(reg, scope="global", h=4, wire_bytes=1000.0,
                           loss=0.5, round_s=0.2, sync_s=0.05,
                           stage_s=[(0, 0.03), (1, 0.02)])
    text = reg.exposition()
    for frag in ("repro_wire_bytes_total 1000", "repro_h 4",
                 'repro_rounds_total{scope="global"} 1',
                 'repro_stage_time_seconds{scope="global",stage="0"} 0.03',
                 "repro_worker_step_skew 0", "repro_loss 0.5"):
        assert frag in text, frag


def test_worker_skew_gauge():
    reg = tmetrics.MetricsRegistry()
    tmetrics.observe_worker_times(reg, [1.0, 1.0, 2.0, 1.0])
    text = reg.exposition()
    assert "repro_worker_step_skew 0.8" in text   # (2-1)/1.25
    tmetrics.observe_worker_times(reg, None)      # lockstep simulator
    assert "repro_worker_step_skew 0" in reg.exposition()


# ---------------------------------------------------------------------------
# exporters + validators
# ---------------------------------------------------------------------------

def test_perfetto_trace_passes_chrome_validator():
    tr = ttrace.Tracer()
    with tr.span("round", step=0, h=2):
        with tr.span("sync", scope="global"):
            pass
    tr.start("eval")                    # left open: must be skipped
    obj = texport.perfetto_trace(tr, extra={"wall_s": 1.0})
    assert texport.validate_chrome_trace(obj) == []
    assert len(obj["traceEvents"]) == 2
    ev = {e["name"]: e for e in obj["traceEvents"]}
    assert ev["round"]["ph"] == "X" and ev["round"]["args"]["h"] == 2
    assert ev["round"]["cat"] == "train"
    assert obj["otherData"] == {"wall_s": 1.0}
    # microsecond timebase: sync starts at/after round
    assert ev["sync"]["ts"] >= ev["round"]["ts"]


def test_chrome_validator_catches_malformed():
    assert texport.validate_chrome_trace([]) != []
    assert texport.validate_chrome_trace({"traceEvents": [{}]}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0,
                            "pid": 1, "tid": 0}]}        # X without dur
    assert any("dur" in e for e in texport.validate_chrome_trace(bad))


def test_jsonl_validator():
    good = {k: 1 for k in texport.JSONL_REQUIRED}
    good["topology"] = "flat"
    assert texport.validate_round_jsonl([json.dumps(good)]) == []
    # traced schema additionally requires the *_s fields
    errs = texport.validate_round_jsonl([json.dumps(good)], traced=True)
    assert any("round_s" in e for e in errs)
    traced = dict(good, round_s=0.1, sync_s=0.05, stage_s={"0": 0.05})
    assert texport.validate_round_jsonl([json.dumps(traced)]) == []
    # autodetect: first record carries round_s => whole file must
    assert texport.validate_round_jsonl(
        [json.dumps(traced), json.dumps(good)]) != []
    bad = dict(traced, stage_s={"0": "fast"})
    assert any("stage_s" in e
               for e in texport.validate_round_jsonl([json.dumps(bad)]))
    missing = dict(good)
    missing.pop("wire_bytes")
    assert any("wire_bytes" in e
               for e in texport.validate_round_jsonl([json.dumps(missing)]))


def test_run_manifest_fields():
    run = _quad_run(steps=8)
    m = texport.run_manifest(run=run, plan=_plan())
    assert m["schema"] == "repro.run_manifest/1"
    assert m["config_hash"] == texport.config_hash(run)
    assert len(m["config_hash"]) == 16
    assert m["backend"] == jax.default_backend()
    assert m["plan"]["topology"] and m["plan"]["num_workers"] == W
    assert m["local_sgd"]["local_steps"] == run.local_sgd.local_steps
    # the hash moves when the config moves
    import dataclasses
    run2 = dataclasses.replace(run, steps=run.steps + 1)
    assert texport.config_hash(run2) != m["config_hash"]


# ---------------------------------------------------------------------------
# fit-level acceptance
# ---------------------------------------------------------------------------

QUAD_SPECS = {"w": ParamSpec((D, C), (None, None)),
              "b": ParamSpec((C,), (None,), init="zeros")}


def quad_loss(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, {"xent": loss}


def quad_batches(seed=1, b=8):
    i = 0
    while True:
        k = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        x = jax.random.normal(k, (W, b, D))
        y = x @ (jnp.ones((D, C)) * 0.5) + 0.01 * jax.random.normal(
            jax.random.fold_in(k, 1), (W, b, C))
        yield {"x": x, "y": y}
        i += 1


def _quad_run(H=2, steps=12, controller=None, **ls_kw):
    ls_kw.setdefault("sync_compression", "sign")
    return RunConfig(
        model=ModelConfig(name="quad", family="dense", citation=""),
        shape=InputShape("t", 8, W * 4, "train"),
        local_sgd=LocalSGDConfig(local_steps=H, local_momentum=0.9,
                                 nesterov=True, wire_pack=True, **ls_kw),
        optim=OptimConfig(base_lr=0.03, base_batch=W * 4, weight_decay=0.0,
                          lr_warmup_steps=0, lr_decay_steps=()),
        controller=controller or ControllerConfig(),
        steps=steps)


def _quad_bundle(run):
    cc = run.controller
    init, local_step, sync = make_local_sgd(
        run, quad_loss, num_workers=W, use_kernel=True,
        telemetry=cc.wants_telemetry,
        speculate_compression=cc.wants_speculation)
    nb = flatbuf.build_layout(
        {"w": jax.ShapeDtypeStruct((D, C), jnp.float32),
         "b": jax.ShapeDtypeStruct((C,), jnp.float32)}).num_buckets
    return TrainBundle(cfg=run.model, run=run, layout=None, num_workers=W,
                       specs=QUAD_SPECS, init=init, local_step=local_step,
                       sync=sync, telemetry=cc.wants_telemetry, n_comp=nb)


def test_traced_fit_emits_validated_artifacts(tmp_path):
    """ISSUE-8 acceptance: one traced smoke fit produces a
    Perfetto-loadable trace whose per-stage sync spans carry the same
    stage ids the ledger prices, a Prometheus exposition with the
    step-time and worker-skew series, the extended JSONL, and the run
    manifest — all passing the CI validators."""
    steps = 12
    run = _quad_run(steps=steps)
    tr = ttrace.Tracer(metrics=tmetrics.MetricsRegistry())
    tlog = tmp_path / "telemetry.jsonl"
    state, hist, summary = fit(
        run, quad_batches(), bundle=_quad_bundle(run), num_steps=steps,
        telemetry_path=str(tlog), tracer=tr,
        manifest_path=str(tmp_path / "manifest.json"),
        eval_every=4, eval_fn=lambda s: {"probe": 0.0},
        log=lambda *a, **k: None)

    names = {s.name for s in tr.spans}
    assert {"round", "local_steps", "sync", "collective",
            "controller", "eval"} <= names
    rounds = steps // run.local_sgd.local_steps
    assert sum(s.name == "round" for s in tr.spans) == rounds
    assert sum(s.name == "local_steps" for s in tr.spans) == steps

    # (a) trace: valid + per-stage spans join the ledger's stage rows
    obj = texport.write_perfetto(str(tmp_path / "trace.json"), tr)
    assert texport.validate_chrome_trace(obj) == []
    col = [s for s in tr.spans if s.name == "collective"]
    n_stages = len({s.attrs["stage"] for s in col})
    assert n_stages >= 1
    assert summary["ledger"]["sync_rounds"] == rounds
    assert summary["ledger"]["sync_seconds"] > 0
    # every collective span's stage id is a priced ledger stage id
    assert {s.attrs["stage"] for s in col} == set(range(n_stages))

    # (b) prometheus: step-time + skew series present
    text = texport.write_prometheus(str(tmp_path / "metrics.prom"), tr.metrics)
    assert f"repro_step_time_seconds_count {steps}" in text
    assert "repro_worker_step_skew 0" in text
    assert 'repro_sync_time_seconds_count{scope="global"} ' \
        f"{rounds}" in text

    # (c) JSONL extended schema + manifest, via the CI directory gate
    recs = [json.loads(l) for l in tlog.read_text().splitlines()]
    assert len(recs) == rounds
    for r in recs:
        assert r["sync_s"] >= 0
        assert r["round_s"] >= r["sync_s"]   # round window contains sync
        assert set(r["stage_s"]) == {str(i) for i in range(n_stages)}
        np.testing.assert_allclose(sum(r["stage_s"].values()), r["sync_s"],
                                   rtol=1e-6)
    assert texport.check_trace_dir(str(tmp_path)) == []
    assert summary["trace"]["spans"] == len(tr.spans)


def test_tracing_is_bitwise_noop(tmp_path):
    """The regression gate: fit with a fenced tracer (+ metrics + JSONL)
    vs. fit with no tracer — parameter trajectories BITWISE identical.
    Tracing is observation only."""
    steps = 8
    mk = lambda: (_quad_run(steps=steps), quad_batches())
    run_a, it_a = mk()
    st_a, _, _ = fit(run_a, it_a, bundle=_quad_bundle(run_a),
                     num_steps=steps, log=lambda *a, **k: None)
    run_b, it_b = mk()
    tr = ttrace.Tracer(fence=True, annotate=True,
                       metrics=tmetrics.MetricsRegistry())
    st_b, _, _ = fit(run_b, it_b, bundle=_quad_bundle(run_b),
                     num_steps=steps, tracer=tr,
                     telemetry_path=str(tmp_path / "t.jsonl"),
                     log=lambda *a, **k: None)
    assert tr.spans                      # the traced run really traced
    for a, b in zip(jax.tree.leaves(st_a.params), jax.tree.leaves(st_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_traced_noise_adaptive_controller_spans(tmp_path):
    """Controller decision spans carry the emitted PlanDelta."""
    steps = 16
    run = _quad_run(H=2, steps=steps, sync_compression="ef_sign",
                    controller=ControllerConfig(kind="noise_adaptive",
                                                patience=1, h_max=8,
                                                err_budget=0.95))
    tr = ttrace.Tracer()
    fit(run, quad_batches(), bundle=_quad_bundle(run), num_steps=steps,
        tracer=tr, log=lambda *a, **k: None)
    ctl = [s for s in tr.spans if s.name == "controller"]
    assert ctl and all(s.attrs["kind"] == "noise_adaptive" for s in ctl)
    for s in ctl:
        assert {"next_h", "compression", "batch_scale", "lr_scale",
                "decisions"} <= set(s.attrs)
    # decisions trace the sensor->actuator provenance at least once
    assert any(s.attrs["decisions"] for s in ctl)


# ---------------------------------------------------------------------------
# span tree, compile counters and the shared clock
# ---------------------------------------------------------------------------

DRIVER_LEAVES = ("fit_setup", "batch", "step_dispatch", "readback", "ledger")


def test_spans_link_to_their_parent_and_self_time_subtracts_children():
    tr = ttrace.Tracer()
    with tr.span("round") as rd:
        with tr.span("local_steps") as ls:
            with tr.span("batch") as b:
                pass
            with tr.span("step_dispatch") as d:
                pass
        with tr.span("sync") as sy:
            pass
    with tr.span("readback") as rb:
        pass
    assert rd.parent is None and rb.parent is None
    assert ls.parent == rd.id and sy.parent == rd.id
    assert b.parent == ls.id and d.parent == ls.id
    assert len({s.id for s in tr.spans}) == len(tr.spans)
    for s in (rd, ls, b, d, sy, rb):        # pin for exact arithmetic
        s.dur_s = {rd.id: 10.0, ls.id: 6.0, b.id: 1.0, d.id: 4.0,
                   sy.id: 3.0, rb.id: 2.0}[s.id]
    rd.ts_s, ls.ts_s, b.ts_s, d.ts_s, sy.ts_s = 0.0, 0.0, 0.5, 1.5, 6.5
    own = ttrace.self_time_s(tr.spans)
    assert own[ls.id] == pytest.approx(1.0)      # 6 - (1 + 4)
    assert own[rd.id] == pytest.approx(1.0)      # 10 - (6 + 3)
    assert own[b.id] == pytest.approx(1.0) and own[rb.id] == 2.0


def test_finish_out_of_order_leaves_the_open_stack_consistent():
    tr = ttrace.Tracer()
    rd = tr.start("round")
    ls = tr.start("local_steps")
    tr.finish(rd, incomplete=True)       # the mid-round exit path
    with tr.span("batch") as b:
        pass
    assert b.parent == ls.id
    tr.finish(ls)
    with tr.span("readback") as rb:
        pass
    assert rb.parent is None
    rec = tr.record("collective", 0.0, 1.0, parent=ls.id, stage=0)
    assert rec.parent == ls.id and rec.attrs == {"stage": 0}


def _jit_quad_bundle(run):
    """The quad bundle with its local step and sync jitted, as
    ``build_train`` jits them: a fresh bundle traces on first use."""
    init, local_step, sync = make_local_sgd(run, quad_loss, num_workers=W,
                                            use_kernel=False)
    jsync = jax.jit(lambda s, plan, scope: sync(s, plan=plan, scope=scope),
                    static_argnums=(1, 2))
    return TrainBundle(cfg=run.model, run=run, layout=None, num_workers=W,
                       specs=QUAD_SPECS, init=init,
                       local_step=jax.jit(local_step),
                       sync=lambda s, *, plan=None, scope=None:
                           jsync(s, plan, scope),
                       telemetry=False, n_comp=1)


def test_traced_fit_puts_every_driver_stretch_in_a_leaf_span():
    steps, H = 6, 2
    run = _quad_run(H=H, steps=steps, sync_compression="none")
    tr = ttrace.Tracer()
    fit(run, quad_batches(), bundle=_jit_quad_bundle(run), num_steps=steps,
        tracer=tr, log=lambda *a, **k: None)
    by_id = {s.id: s for s in tr.spans}
    count = {n: sum(s.name == n for s in tr.spans) for n in DRIVER_LEAVES}
    assert count == {"fit_setup": 1, "batch": steps, "step_dispatch": steps,
                     "readback": steps, "ledger": steps // H}
    setup = next(s for s in tr.spans if s.name == "fit_setup")
    assert setup.parent is None
    # params, momentum, step and rng of the quad model's state
    assert setup.attrs["state_leaves"] == 2 + 2 + 1 + 1
    eps = 1e-6
    for s in tr.spans:
        if s.name in ("batch", "step_dispatch"):
            assert by_id[s.parent].name == "local_steps"
        if s.name == "local_steps":
            rd = by_id[s.parent]
            assert rd.name == "round"
            kids = [k for k in tr.spans if k.parent == s.id]
            assert {k.name for k in kids} == {"batch", "step_dispatch"}
            assert sum(k.dur_s for k in kids) <= s.dur_s + eps
            assert s.dur_s <= rd.dur_s + eps
        if s.name in ("readback", "ledger"):
            assert s.parent is None or by_id[s.parent].name == "round"
    assert ttrace.self_time_s(tr.spans)[setup.id] == pytest.approx(
        setup.dur_s)


class _DoubleBatchAfterRoundOne(StaticController):
    """Static H; the batch doubles once the first round is reported."""

    def __init__(self, run):
        super().__init__(run)
        self.scale = 1

    def batch_scale(self) -> int:
        return self.scale

    def update(self, report):
        self.scale = 2


def test_a_retrace_shows_on_the_step_dispatch_that_paid_for_it():
    """A fresh bundle traces its step on the first call; a new batch
    scale traces it again; steady steps trace nothing."""
    steps, H = 6, 2
    run = _quad_run(H=H, steps=steps, sync_compression="none")
    tr = ttrace.Tracer()
    _, _, summary = fit(run, quad_batches(), bundle=_jit_quad_bundle(run),
                        num_steps=steps, tracer=tr,
                        controller=_DoubleBatchAfterRoundOne(run),
                        log=lambda *a, **k: None)
    by_id = {s.id: s for s in tr.spans}
    traces = {by_id[s.parent].attrs["step"]: s.attrs.get("traces", 0)
              for s in tr.spans if s.name == "step_dispatch"}
    assert traces[0] >= 1 and traces[H] >= 1
    assert all(traces[t] == 0 for t in range(steps) if t not in (0, H))
    assert summary["trace"]["traces"] >= traces[0] + traces[H]
    assert summary["trace"]["compiles"] >= 2
    assert {"traces", "compiles", "cache_loads"} <= set(summary["trace"])


def test_jit_event_totals_reach_the_prometheus_exposition():
    run = _quad_run(H=2, steps=2, sync_compression="none")
    tr = ttrace.Tracer(metrics=tmetrics.MetricsRegistry())
    _, _, summary = fit(run, quad_batches(), bundle=_jit_quad_bundle(run),
                        num_steps=2, tracer=tr, log=lambda *a, **k: None)
    text = tr.metrics.exposition()
    for kind in ("traces", "compiles", "cache_loads"):
        n = summary["trace"][kind]
        assert f'repro_jit_events_total{{kind="{kind}"}} {n}' in text


def test_null_tracer_records_nothing_and_registers_no_listener(monkeypatch):
    import jax.monitoring
    calls = []
    monkeypatch.setattr(ttrace, "_listening", False)
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        calls.append)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener", calls.append)
    run = _quad_run(H=2, steps=2, sync_compression="none")
    fit(run, quad_batches(), bundle=_jit_quad_bundle(run), num_steps=2,
        log=lambda *a, **k: None)
    ttrace.NullTracer()
    assert calls == [] and ttrace.NULL.spans == []
    ttrace.Tracer()
    ttrace.Tracer()
    assert len(calls) == 2                  # once per process


def _profiled_spans(trace_dir, names):
    import glob
    import os

    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        s = int(ev.start_ns)
                        out.append((s, s + int(ev.duration_ns), ev.name))
    return out


def test_clock_offset_lays_tracer_spans_on_a_profile_capture(tmp_path):
    import time
    names = ("round", "local_steps", "sync")
    tr = ttrace.Tracer(annotate=True)
    x = jnp.full((64, 64), 0.01)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(4):
            with tr.span("round"):
                with tr.span("local_steps"):
                    x = jnp.tanh(x @ x).block_until_ready()
                time.sleep(0.002)
                with tr.span("sync"):
                    x = (x * 0.5).block_until_ready()
    profiled = _profiled_spans(str(tmp_path), names)
    off = ttrace.clock_offset_ns(tr.spans, profiled)
    assert off is not None
    for name in names:
        mine = sorted((s.ts_s, s.dur_s) for s in tr.spans if s.name == name)
        theirs = sorted((s, e) for s, e, n in profiled if n == name)
        assert len(mine) == len(theirs) == 4
        for (ts, dur), (s, e) in zip(mine, theirs):
            assert abs(s - (ts * 1e9 + off)) < 0.5e6
            assert abs(e - ((ts + dur) * 1e9 + off)) < 0.5e6
    # names whose counts disagree are not paired; nothing left -> None
    rounds = [p for p in profiled if p[2] == "round"]
    assert ttrace.clock_offset_ns(tr.spans, rounds[:-1]) is None
    assert ttrace.clock_offset_ns(tr.spans, []) is None
    assert ttrace.clock_offset_ns([], profiled) is None
