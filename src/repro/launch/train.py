"""Training driver: the paper's schedules on top of the step builders.

``fit`` runs mini-batch SGD / local SGD / post-local SGD / hierarchical
local SGD purely by LocalSGDConfig — the communication pattern is decided
host-side exactly like the paper's Alg. 1/2/5 outer loops — and, with a
non-static ``ControllerConfig``, closes the loop (ISSUE 3): per-round
telemetry (repro/telemetry) feeds a ``SyncController``
(core/controller.py) that drives H(t), the sync compressor, and the
per-worker batch size at each global sync boundary.  Every global round
is appended to the comms ledger and (optionally) one JSONL line in
``telemetry_path`` (schema: the RoundReport fields + the
``round_summary`` stats + ledger costs + the controller's NEXT
decisions).

With a ``telemetry.trace.Tracer`` (ISSUE 8) the loop is additionally
span-instrumented — ``round`` / ``local_steps`` / ``sync`` (+ per-stage
``collective`` attribution) / ``controller`` / ``eval`` / ``checkpoint``
— feeding the Perfetto/Prometheus exporters, the per-stage ``stage_s``
seconds in the ledger rows and JSONL, and a run manifest beside the
JSONL.  Without a tracer the loop runs the exact untraced code path
(pinned bitwise by tests/test_trace.py).

CLI (end-to-end example entry point):
    PYTHONPATH=src python -m repro.launch.train --arch paper-lm --steps 200
    PYTHONPATH=src python -m repro.launch.train --smoke --steps 20 \
        --trace-dir traced_run    # + trace.json/metrics.prom/manifest.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro import telemetry as tele
from repro.configs.base import InputShape, LocalSGDConfig, OptimConfig, RunConfig
from repro.core import syncplan as splan
from repro.core.controller import RoundReport, make_controller, traced_decision
from repro.core.schedule import DynamicSchedule
from repro.telemetry import metrics as tmetrics
from repro.data.partition import ShardedBatches
from repro.data.synthetic import lm_examples, markov_lm
from repro.models import base as mbase
from repro.models import lm


def _sync_layout(state):
    """Per-worker flatbuf layout of the synced state (ledger cost model)."""
    from repro.core import flatbuf
    from repro.core.local_sgd import is_resident
    if is_resident(state):
        return state.params.layout
    return flatbuf.build_layout(state.params, leading=1)


def _scaled_batch(data_iter, scale: int):
    """Concatenate ``scale`` batches along the local-batch dim (axis 1 of
    the (W, B_loc, ...) leaves) — the adaptive_batch controller's
    actuator.  Each distinct scale compiles the step once."""
    if scale <= 1:
        return next(data_iter)
    parts = [next(data_iter) for _ in range(scale)]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1), *parts)


def fit(run: RunConfig, data_iter, *, bundle=None, num_steps=None, seed=0,
        eval_every=0, eval_fn=None, log=print, mesh=None, layout=None,
        controller=None, telemetry_path=None, tracer=None,
        checkpoint_every=0, checkpoint_fn=None, manifest_path=None,
        backend=None):
    """Run the full schedule; returns (state, history, summary).

    ``controller`` overrides the policy built from ``run.controller``;
    ``telemetry_path`` writes one JSON line per global sync round.
    ``tracer`` (a ``telemetry.trace.Tracer``) span-instruments the loop
    and — when it carries a metrics registry — feeds the Prometheus set;
    traced runs extend the JSONL records with ``round_s``/``sync_s``/
    ``stage_s`` and write a run manifest at ``manifest_path`` (default:
    ``<telemetry_path>.manifest.json``).  ``checkpoint_fn(state, step)``
    runs every ``checkpoint_every`` steps inside a ``checkpoint`` span.

    ``backend`` (ISSUE 9) is the execution substrate owning the
    WorkerSet (repro.backend).  None builds the default ``LocalBackend``
    from ``mesh``/``layout`` — bitwise-identical to the pre-seam path.
    The backend feeds the per-worker step times into the round stats
    (``worker_step_skew``) and actuates the elastic PlanDelta fields:
    ``demote`` (census + outer-scope scheduling), ``block_steps``
    (DynamicSchedule cadence), and ``workers`` (resize: state surgery
    via core/elastic, bundle/plan rebuild through the backend, data
    re-partition, and the Lau et al. 2024 LR co-scaling).  Legacy
    callers — ``fit(run, data_iter)`` or hand-made bundles without a
    ``worker_set`` — keep working through the default-backend shim (the
    latter with a DeprecationWarning, mirroring the PR 5 ``sync(group=)``
    treatment).
    """
    from repro.backend.local import LocalBackend
    if backend is None:
        backend = LocalBackend(mesh=mesh, layout=layout)
    elif mesh is None:
        mesh = getattr(backend, "mesh", None)
    if bundle is None:
        bundle = backend.build(run)
    elif hasattr(backend, "adopt"):
        backend.adopt(bundle)
    if mesh is None and bundle.layout is not None:
        # a bundle built on a mesh carries it in its bound layout: the
        # ledger then prices every round from the compiled sync's HLO
        mesh = bundle.layout.mesh
    num_steps = num_steps or run.steps
    ls = run.local_sgd

    rng = jax.random.PRNGKey(seed)
    params0 = mbase.materialize(bundle.specs, rng,
                                dtype=jnp.dtype(run.model.param_dtype))
    state = bundle.init(jax.random.fold_in(rng, 1), params0)

    controller = controller or make_controller(run, n_comp=bundle.n_comp)
    sched = DynamicSchedule(ls, controller.h_at)
    ledger = tele.CommsLedger()
    cost_cache: dict = {}
    # the round plan: built once by build_train (bundle.sync_plan) or —
    # for hand-made bundles — compiled here from the state's own bucket
    # layout with the config's declared topology.  The controller
    # rewrites it between rounds via PlanDelta.
    def config_plan(bundle_, state_):
        from repro.core.local_sgd import needs_anchor
        wa = (bundle_.layout.worker_axes if bundle_.layout is not None else ())
        return splan.make_sync_plan(
            _sync_layout(state_),
            topology=splan.resolve_topology(ls, bundle_.num_workers,
                                            worker_axes=wa),
            compression=ls.sync_compression, num_workers=bundle_.num_workers,
            wire_pack=ls.wire_pack, coalesce=ls.sync_coalesce,
            worker_axes=wa, anchored=needs_anchor(ls))

    plan = bundle.sync_plan
    if plan is None:
        plan = config_plan(bundle, state)
    # align round 1 with the controller's INITIAL decision: the
    # error-driven compressor policies (auto_compress, noise_adaptive)
    # start uncompressed and escalate from measured error, so the
    # config's declared wire format must not leak into the first sync.
    # Identity policies emit an empty rewrite and the config plan
    # passes through as the SAME object (static stays bitwise).
    plan = controller.plan_delta(0).apply(plan)
    # abstract avals of the state, for lowering sync in the ledger cost
    # path — holding the concrete init state alive here would pin a
    # second full optimizer state in device memory for the whole run
    state_avals = jax.eval_shape(lambda s: s, state)

    def measured_cost(p, scope):
        """Ledger pricing: stage rows come from the plan's ring-model
        estimates; on a mesh the compiled sync's HLO supplies the
        MEASURED round total (tele.hlo_sync_cost) — cross-checked
        against the plan estimate, since a large deviation means the
        lowering moved bytes the plan didn't predict (e.g. a stray
        dense gather).  Returns the HLO SyncCost or None (analytic)."""
        key = (p, scope)
        if key not in cost_cache:
            cost = None
            if mesh is not None and bundle.sync_lower is not None:
                est_bytes, _ = p.scope_cost(scope)
                try:
                    # one extra sync compile per (plan, scope) key
                    # (cached); executing this AOT object instead of the
                    # jitted sync would drop jit's auto-resharding of
                    # host-resident init arrays, so the dispatch path
                    # keeps its own compile
                    txt = (bundle.sync_lower(state_avals, plan=p,
                                             scope=scope)
                           .compile().as_text())
                    cost = tele.hlo_sync_cost(txt)
                except Exception as e:       # lowering quirks: keep analytic
                    log(f"ledger: hlo sync cost unavailable ({e!r}); "
                        "using the plan's ring-model estimates")
                else:
                    ratio = cost.bytes_on_wire / max(est_bytes, 1.0)
                    if not 1 / 3 <= ratio <= 3 and est_bytes:
                        log(f"ledger: measured sync bytes "
                            f"{cost.bytes_on_wire:.3g} deviate from the "
                            f"plan's ring-model estimate "
                            f"{est_bytes:.3g} (x{ratio:.2f})")
            cost_cache[key] = cost
        return cost_cache[key]

    tracer = tracer if tracer is not None else tele.NULL
    mreg = tracer.metrics
    if tracer.enabled and (manifest_path or telemetry_path):
        # the reproducibility sidecar BESIDE the JSONL: written up front
        # so a crashed run still identifies itself to the trend tooling
        from repro.telemetry import export as texport
        texport.write_run_manifest(
            manifest_path or f"{telemetry_path}.manifest.json",
            run=run, plan=plan, layout=bundle.layout, mesh=mesh)

    tlog = None
    history = []
    comm_rounds = {"block": 0, "global": 0}
    global_rounds = 0
    # the runtime LR multiplier is a product of two factors: the
    # controller's absolute lr_scale (PlanDelta.lr_scale — the
    # noise_adaptive batch-cap handoff) and the cumulative elastic
    # co-scaling factor (linear scaling with the global batch across
    # worker-set resizes, Lau et al. 2024).  Both at 1.0 keeps the
    # exact two-arg local_step call so static trajectories stay
    # bitwise-identical (and custom bundles without the lr_scale arg
    # keep working).
    lr_ctrl = 1.0
    lr_resize = 1.0
    lr_scale_now = 1.0
    resizes = 0
    # one "round" span per global round: opened at the round's first
    # local step, closed when its global sync (+ decision) completes
    round_span = None
    t_start = time.perf_counter()
    try:
        # opened inside the try so a raise anywhere in the loop (or in
        # the ledger cost path) cannot leak the JSONL handle
        if telemetry_path:
            tlog = open(telemetry_path, "w")
        for t in range(num_steps):
            h_now = max(int(controller.h_at(t)), 1)
            if round_span is None:
                round_span = tracer.start("round", round=global_rounds + 1,
                                          step=t, h=h_now)
            with tracer.span("local_steps", step=t) as stp:
                batch = _scaled_batch(data_iter, controller.batch_scale())
                if lr_scale_now == 1.0:
                    state, metrics = bundle.local_step(state, batch)
                else:
                    state, metrics = bundle.local_step(state, batch,
                                                       lr_scale_now)
                stp.fence(state)
            if mreg is not None:
                tmetrics.observe_step(mreg, stp.dur_s)
            level = sched.advance(t)
            synced = ""
            if level == 1:
                with tracer.span("sync", scope="block",
                                 topology=plan.topology.describe()) as ssp:
                    state = bundle.sync(state, plan=plan, scope="block")
                    ssp.fence(state)
                stage_s = tele.sync_stage_spans(tracer, plan, "block", ssp)
                entry = ledger.record_plan(
                    step=t, level=1, h=h_now, plan=plan, scope="block",
                    measured=measured_cost(plan, "block"),
                    seconds=ssp.dur_s, num_workers=bundle.num_workers)
                comm_rounds["block"] += 1
                synced = "block"
                if mreg is not None:
                    tmetrics.observe_round(
                        mreg, scope="block", h=h_now,
                        wire_bytes=entry["bytes_on_wire"],
                        sync_s=ssp.dur_s, stage_s=stage_s)
            elif level == 2:
                # the plan already carries last round's PlanDelta
                # (compressor modes / topology) — no loose kwargs
                with tracer.span("sync", scope="global",
                                 topology=plan.topology.describe()) as ssp:
                    state = bundle.sync(state, plan=plan, scope="global")
                    ssp.fence(state)
                sync_s = ssp.dur_s
                stage_s = tele.sync_stage_spans(tracer, plan, "global", ssp)
                global_rounds += 1
                entry = ledger.record_plan(
                    step=t, level=2, h=h_now, plan=plan, scope="global",
                    measured=measured_cost(plan, "global"),
                    batch_scale=controller.batch_scale(),
                    lr_scale=lr_scale_now, seconds=sync_s,
                    num_workers=bundle.num_workers)
                comm_rounds["global"] += 1
                synced = "global"
                stats = (tele.round_summary(state.stats)
                         if bundle.telemetry else {})
                # backend step-time census: None on lockstep backends
                # (one vmap, one clock — the gauge stays 0.0); the
                # simulated/distributed backends report per-ACTIVE-worker
                # seconds, the straggler sensor for the elastic policy
                wtimes = backend.worker_step_times(h=h_now,
                                                  measured_s=stp.dur_s)
                if wtimes:
                    ts = [float(x) for x in wtimes]
                    mean_t = sum(ts) / len(ts)
                    ws = backend.worker_set
                    active = ws.active or ws.ids
                    stats["worker_step_s"] = ts
                    stats["worker_step_skew"] = (
                        (max(ts) - min(ts)) / mean_t if mean_t > 0 else 0.0)
                    stats["worker_slowest"] = int(
                        active[max(range(len(ts)), key=ts.__getitem__)])
                    stats.setdefault("num_workers", ws.num_workers)
                # by-id census covers demoted workers too — the sensor
                # the promotion-back path needs (a recovered straggler
                # is invisible in the active-only skew above)
                wtimes_by_id = backend.worker_times_by_id(
                    h=h_now, measured_s=stp.dur_s)
                if wtimes_by_id:
                    stats["worker_step_s_by_id"] = {
                        int(k): float(v) for k, v in wtimes_by_id.items()}
                report = RoundReport(
                    round=global_rounds, step=t, h=h_now,
                    loss=float(metrics["loss"]),
                    stats=stats,
                    wire_bytes=entry["bytes_on_wire"],
                    collectives=entry["collectives"])
                delta = traced_decision(tracer, controller, report, t + 1)
                plan = delta.apply(plan)
                if getattr(delta, "lr_scale", None) is not None:
                    lr_ctrl = float(delta.lr_scale)
                    lr_scale_now = lr_ctrl * lr_resize
                tracer.finish(round_span, loss=report.loss,
                              wire_bytes=report.wire_bytes)
                round_s = round_span.dur_s
                round_span = None
                if mreg is not None:
                    tmetrics.observe_round(
                        mreg, scope="global", h=h_now,
                        wire_bytes=report.wire_bytes, loss=report.loss,
                        batch_scale=controller.batch_scale(),
                        lr_scale=lr_scale_now, round_s=round_s,
                        sync_s=sync_s, stage_s=stage_s,
                        worker_step_s=wtimes)
                if tlog is not None:
                    # None delta fields mean "keep": log the effective
                    # next decision, not the literal None
                    rec = {"round": report.round, "step": t, "h": h_now,
                           "loss": report.loss, **report.stats,
                           "wire_bytes": report.wire_bytes,
                           "collectives": report.collectives,
                           "cum_wire_bytes": ledger.total_bytes(),
                           "next_h": int(delta.h if delta.h is not None
                                         else controller.h_at(t + 1)),
                           "next_compression": _mode_str(delta.compression),
                           "next_batch_scale": int(
                               delta.batch_scale
                               if delta.batch_scale is not None
                               else controller.batch_scale()),
                           "next_lr_scale": lr_scale_now,
                           "topology": plan.topology.describe()}
                    if getattr(delta, "workers", None) is not None:
                        rec["next_workers"] = int(delta.workers)
                    if getattr(delta, "demote", None) is not None:
                        rec["demote"] = int(delta.demote)
                    if getattr(delta, "promote", None) is not None:
                        rec["promote"] = int(delta.promote)
                    if tracer.enabled:
                        # the seconds extension of the schema (README):
                        # round/sync wall time + per-stage attribution
                        # keyed by the SAME stage ids the ledger prices
                        rec["round_s"] = round_s
                        rec["sync_s"] = sync_s
                        rec["stage_s"] = {str(i): s for i, s in stage_s}
                    # decision provenance (noise_adaptive): which sensor
                    # drove which actuation this round
                    prov = getattr(controller, "decisions", None)
                    if prov:
                        rec["decisions"] = prov
                    tlog.write(json.dumps(rec) + "\n")
                    tlog.flush()
                # --- elastic actuation (ISSUE 9): the worker-set fields
                # of the PlanDelta, applied AFTER the round is fully
                # recorded so the JSONL/trace show the decision at the
                # round that made it and the next round runs under the
                # new census ---------------------------------------------
                if getattr(delta, "demote", None) is not None:
                    backend.demote(int(delta.demote))
                if getattr(delta, "promote", None) is not None:
                    backend.promote(int(delta.promote))
                if getattr(delta, "block_steps", None) is not None:
                    sched.block_steps = int(delta.block_steps)
                new_w = getattr(delta, "workers", None)
                if new_w is not None and int(new_w) != bundle.num_workers:
                    new_w, old_w = int(new_w), bundle.num_workers
                    with tracer.span("resize", step=t, from_workers=old_w,
                                     to_workers=new_w):
                        from repro.core import elastic
                        # carry the resident/tree state across the new
                        # worker axis (departing workers' momentum/EF
                        # folded via the mean, joiners cloned)
                        state = elastic.resize_state(state, new_w)
                        bundle = backend.resize(run, new_w)
                        state_avals = jax.eval_shape(lambda s: s, state)
                        # recompile the plan for the new W, carrying the
                        # controller's current modes; a block size that
                        # no longer divides W is re-derived
                        topo = plan.topology
                        if topo.block_size and new_w % topo.block_size:
                            topo = splan.Topology(
                                topo.kind, splan.default_block_size(new_w))
                        newplan = bundle.sync_plan
                        if newplan is None:
                            newplan = config_plan(bundle, state)
                        plan = (newplan.with_modes(plan.modes)
                                .with_topology(topo))
                        if hasattr(data_iter, "resize"):
                            data_iter.resize(new_w)
                        else:
                            raise RuntimeError(
                                f"elastic resize {old_w} -> {new_w} needs a "
                                "resizable data iterator (ShardedBatches or "
                                "any object with .resize(num_workers)); got "
                                f"{type(data_iter).__name__}")
                        # LR co-scales with the global batch (linear
                        # scaling across the resize, Lau et al. 2024)
                        lr_resize *= new_w / old_w
                        lr_scale_now = lr_ctrl * lr_resize
                        resizes += 1
                    log(f"resize: W {old_w} -> {new_w} at step {t} "
                        f"(lr x{lr_resize:g})")
            rec = {k: float(v) for k, v in metrics.items()}
            rec.update(step=t, synced=synced)
            history.append(rec)
            if eval_every and eval_fn and (t + 1) % eval_every == 0:
                with tracer.span("eval", step=t):
                    ev = eval_fn(state)
                rec.update({f"eval_{k}": float(v) for k, v in ev.items()})
                log(f"step {t+1}: loss={rec['loss']:.4f} "
                    + " ".join(f"eval_{k}={float(v):.4f}"
                               for k, v in ev.items()))
            if checkpoint_every and checkpoint_fn \
                    and (t + 1) % checkpoint_every == 0:
                with tracer.span("checkpoint", step=t) as csp:
                    csp.fence(checkpoint_fn(state, t))
    finally:
        if round_span is not None:          # training ended mid-round
            tracer.finish(round_span, incomplete=True)
        if tlog is not None:
            tlog.close()
    wall = time.perf_counter() - t_start
    summary = {"wall_s": wall, "comm_rounds": comm_rounds, "steps": num_steps,
               "topology": plan.topology.describe(),
               "backend": backend.describe(),
               "resizes": resizes,
               "ledger": ledger.summary(),
               "controller": {"kind": getattr(controller, "kind", "custom"),
                              "h_final": int(controller.h_at(num_steps)),
                              "compression": _mode_str(
                                  controller.compression()),
                              "batch_scale": controller.batch_scale(),
                              "lr_scale": lr_scale_now}}
    if tracer.enabled:
        summary["trace"] = {"spans": len(tracer.spans),
                            "fenced": tracer.fence}
    return state, history, summary


def _mode_str(modes) -> str:
    if modes is None:
        return "config"
    if isinstance(modes, str):
        return modes
    return "|".join(modes)


def eval_lm(bundle, data: dict, batch: int = 8):
    """Mean held-out xent of the (averaged) model."""
    cfg = bundle.cfg

    @jax.jit
    def one(params, b):
        loss, m = lm.loss_fn(cfg, params, b, remat="none")
        return m["xent"]

    def fn(state):
        # eval boundary: materializes the pytree view of a resident state
        from repro.core.local_sgd import mean_params
        params = mean_params(state)
        losses = []
        n = len(next(iter(data.values())))
        for i in range(0, min(n, 4 * batch), batch):
            b = {k: jnp.asarray(v[i:i + batch]) for k, v in data.items()}
            losses.append(float(one(params, b)))
        return {"xent": float(np.mean(losses))}
    return fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-lm")
    ap.add_argument("--smoke", action="store_true", help="use reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--local-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--local-steps", type=int, default=4, help="H")
    ap.add_argument("--block-steps", type=int, default=1, help="H^b")
    ap.add_argument("--post-local-switch", type=int, default=-1)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--backend", default="local",
                    choices=["local", "simulated", "distributed"],
                    help="execution backend (repro.backend); simulated "
                         "injects per-worker latency so the straggler "
                         "telemetry has real values on one process")
    ap.add_argument("--straggler-s", type=float, default=0.0,
                    help="simulated backend: extra per-step seconds "
                         "injected into the LAST worker (drives the "
                         "worker_step_skew gauge)")
    ap.add_argument("--controller", default="static",
                    choices=["static", "diversity_h", "adaptive_batch",
                             "auto_compress", "noise_adaptive", "elastic"],
                    help="sync controller policy (elastic adds straggler "
                         "demotion on the skew gauge)")
    ap.add_argument("--trace-dir", default="",
                    help="write trace.json / metrics.prom / manifest.json / "
                         "telemetry.jsonl for this run (Perfetto + "
                         "Prometheus exports; CI validates the schemas)")
    ap.add_argument("--fence", action="store_true",
                    help="block_until_ready at span boundaries: true "
                         "wall-clock per span at the cost of dispatch "
                         "pipelining (defaults off)")
    args = ap.parse_args()
    from repro.utils import enable_compile_cache
    enable_compile_cache()

    cfg = configs.get_smoke(args.arch) if args.smoke or args.arch != "paper-lm" \
        else configs.get("paper-lm")
    cfg = cfg.replace(max_seq_len=args.seq)
    shape = InputShape("cli", args.seq, args.workers * args.local_batch, "train")
    from repro.configs.base import ControllerConfig
    run = RunConfig(
        model=cfg, shape=shape,
        local_sgd=LocalSGDConfig(local_steps=args.local_steps,
                                 block_steps=args.block_steps,
                                 post_local_switch=args.post_local_switch),
        optim=OptimConfig(base_lr=args.lr, base_batch=shape.global_batch,
                          lr_warmup_steps=10,
                          lr_decay_steps=(args.steps // 2, 3 * args.steps // 4)),
        controller=ControllerConfig(kind=args.controller),
        steps=args.steps)

    toks = markov_lm(vocab=cfg.vocab_size, num_seqs=1024, seq_len=args.seq)
    data = lm_examples(toks)
    held = lm_examples(markov_lm(vocab=cfg.vocab_size, num_seqs=64,
                                 seq_len=args.seq, sample_seed=123))
    it = ShardedBatches(data, args.workers, args.local_batch)
    from repro import backend as backend_mod
    be_kw = {}
    if args.backend == "simulated" and args.straggler_s:
        be_kw["latency_s"] = {args.workers - 1: args.straggler_s}
    be = backend_mod.make_backend(args.backend, args.workers, **be_kw)
    bundle = be.build(run)

    tracer = None
    trace_kw = {}
    if args.trace_dir:
        import os
        os.makedirs(args.trace_dir, exist_ok=True)
        tracer = tele.Tracer(fence=args.fence, annotate=True,
                             metrics=tele.MetricsRegistry())
        trace_kw = {"tracer": tracer,
                    "telemetry_path": os.path.join(args.trace_dir,
                                                   "telemetry.jsonl"),
                    "manifest_path": os.path.join(args.trace_dir,
                                                  "manifest.json")}
    state, hist, summary = fit(run, it, bundle=bundle, backend=be,
                               num_steps=args.steps,
                               eval_every=max(args.steps // 5, 1),
                               eval_fn=eval_lm(bundle, held), **trace_kw)
    if tracer is not None:
        import os

        from repro.telemetry import export as texport
        texport.write_perfetto(os.path.join(args.trace_dir, "trace.json"),
                               tracer, extra={"wall_s": summary["wall_s"]})
        texport.write_prometheus(os.path.join(args.trace_dir, "metrics.prom"),
                                 tracer.metrics)
        print(f"trace: {len(tracer.spans)} spans -> {args.trace_dir}/ "
              "(trace.json, metrics.prom, manifest.json, telemetry.jsonl)")
    print(f"done: final loss={hist[-1]['loss']:.4f} wall={summary['wall_s']:.1f}s "
          f"comm={summary['comm_rounds']}")


if __name__ == "__main__":
    main()
