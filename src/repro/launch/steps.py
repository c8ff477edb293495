"""Step builders: wire model zoo + local SGD + layouts into jitted steps.

``build_train(...)`` returns the local-SGD machinery for one arch on one
mesh/layout: init / local_step / sync(+hierarchical) with full
in/out_shardings so the same object serves CPU tests (mesh=None), the
real trainer, and the multi-pod dry-run.

``build_serve(...)`` returns prefill / decode_step for the inference
shapes.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, RunConfig
from repro.core.local_sgd import LocalSGDState, make_local_sgd
from repro.models import base as mbase
from repro.models import lm
from repro.launch import inputs as inp
from repro.sharding.layout import MeshLayout, long_context_serve_layout, serve_layout, train_layout


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


@dataclass
class TrainBundle:
    cfg: ModelConfig
    run: RunConfig
    layout: MeshLayout
    num_workers: int
    specs: Any
    init: Callable
    local_step: Callable
    sync: Callable
    state_shardings: Any = None
    batch_shardings: Any = None
    telemetry: bool = False     # state carries a StatsAccumulator
    n_comp: int = 1             # compression-error slots (sub-buckets)
    step_lower: Any = None      # jit only: lower local_step (compiled text)
    sync_lower: Any = None      # jit only: lower sync (HLO ledger costs)
    sync_plan: Any = None       # compiled syncplan.SyncPlan (fit's default)
    worker_set: Any = None      # backend.base.WorkerSet this bundle was built for


def _stats_partition_specs(layout: MeshLayout):
    """Specs for the telemetry StatsAccumulator: the per-worker (W,)
    accumulators shard over the worker axes (they come out of the
    vmapped per-worker step), everything else is replicated scalars."""
    from repro.telemetry.stats import StatsAccumulator
    wa = layout.worker_axes
    w = wa if len(wa) != 1 else wa[0]
    return StatsAccumulator(
        acc_grad_sq=P(w), acc_update_sq=P(w), acc_steps=P(),
        round_grad_sq=P(w), round_update_sq=P(w), round_steps=P(),
        pre_sync_sq=P(), post_sync_sq=P(),
        comp_err_sq=P(), comp_ref_sq=P(), rounds=P())


def state_partition_specs(specs, layout: MeshLayout, run: RunConfig, *,
                          resident: bool = False, telemetry: bool = False,
                          bucket_layout=None, worker_set=None):
    """PartitionSpecs for a LocalSGDState built from param specs.

    ``worker_set`` is the backend seam: specs name the mesh AXES the
    worker dim shards over (size-agnostic), so the same spec tree serves
    every W — passing the set documents which census the state belongs
    to and lets callers assert the mesh's worker extent matches it.

    ``resident=True`` mirrors the resident bucket form (see
    core/local_sgd): stacked buffers shard their leading worker dim over
    the worker axes, and each sub-bucket's row dim is sharded over its
    sharding-class mesh axes (``flatbuf.bucket_pspec``) — so FSDP/TP
    sub-buckets stay sharded on the bus and single-copy buffers
    (anchor/global_u) are replicated across workers but keep their row
    sharding.  ``telemetry`` mirrors ``make_local_sgd(telemetry=...)``.
    """
    from repro.core.local_sgd import needs_anchor
    ls = run.local_sgd
    stats = _stats_partition_specs(layout) if telemetry else None
    if resident:
        from repro.core import flatbuf
        # ``bucket_layout`` lets build_train pass the ONE abstract
        # bucket layout it already built, so partition specs, n_comp
        # and the resident state can never disagree on the bucketing
        blay = bucket_layout if bucket_layout is not None else \
            flatbuf.build_layout(
                mbase.abstract(specs, jnp.dtype(run.model.param_dtype)),
                wd_mask=mbase.norm_param_mask(specs),
                shard_classes=flatbuf.shard_classes(specs, layout))
        wa = layout.worker_axes
        w = wa if len(wa) != 1 else wa[0]
        nb = blay.num_buckets
        st = lambda: flatbuf.BucketState(
            blay, tuple(flatbuf.bucket_pspec(blay, b, worker=w)
                        for b in range(nb)), leading=1)
        sg = lambda: flatbuf.BucketState(
            blay, tuple(flatbuf.bucket_pspec(blay, b) for b in range(nb)))
        return LocalSGDState(
            params=st(), momentum=st(),
            anchor=sg() if needs_anchor(ls) else None,
            global_u=sg() if ls.global_momentum > 0 else None,
            ef_memory=st() if ls.sync_compression == "ef_sign" else None,
            step=P(), rng=P(), stats=stats)
    stacked = mbase.partition_specs(specs, layout, stacked=True)
    single = mbase.partition_specs(specs, layout, stacked=False)
    return LocalSGDState(
        params=stacked,
        momentum=stacked,
        anchor=single if needs_anchor(ls) else None,
        global_u=single if ls.global_momentum > 0 else None,
        ef_memory=stacked if ls.sync_compression == "ef_sign" else None,
        step=P(),
        rng=P(),
        stats=stats,
    )


def build_train(run: RunConfig, *, mesh: Mesh | None = None,
                layout: MeshLayout | None = None, num_workers: int | None = None,
                use_kernel: bool = False, jit: bool = True,
                worker_set=None) -> TrainBundle:
    cfg = run.model
    if layout is None and mesh is not None:
        worker_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
        layout = train_layout(tuple(mesh.axis_names), worker_axes=worker_axes)
    if layout is not None and mesh is not None:
        layout = layout.with_mesh(mesh)
    if worker_set is not None:
        if num_workers is not None and num_workers != worker_set.num_workers:
            raise ValueError(
                f"num_workers={num_workers} disagrees with "
                f"worker_set ({worker_set.num_workers} workers)")
        num_workers = worker_set.num_workers
    if num_workers is None:
        num_workers = layout.num_workers(mesh) if (mesh is not None and layout) else 1

    specs = lm.param_specs(cfg)
    wd_mask = mbase.norm_param_mask(specs)
    lay_for_model = layout if mesh is not None else None

    def loss(params, batch):
        return lm.loss_fn(cfg, params, batch, lay=lay_for_model, scan=True,
                          remat=run.remat)

    # Flat-bus sync wiring: leaves are classified per (dtype, sharding
    # class) sub-bucket (flatbuf.shard_classes — the EFFECTIVE spec
    # rules, so classification always agrees with placement).  The
    # resident path buckets every leaf, FSDP/TP included; only the
    # non-resident tree path still routes sharded leaves per-leaf
    # (its on-the-fly layouts are replicated).
    from repro.core import flatbuf
    from repro.core import syncplan as splan
    from repro.core.local_sgd import (make_packed_mean,
                                      make_packed_mean_coalesced,
                                      make_packed_mean_flat, pack_axes_tree)
    bucketable = None
    shard_cls = None
    pm = None
    pm_flat = None
    pm_coal = None
    if mesh is not None and layout is not None:
        lay_m = layout
        shard_cls = flatbuf.shard_classes(specs, lay_m)
        bucketable = flatbuf.replicated_tree(shard_cls)
        if run.local_sgd.wire_pack and run.local_sgd.sync_compression != "none":
            # per-leaf path for within-worker-sharded leaves on the
            # NON-resident tree path
            pm = (make_packed_mean(mesh, layout.worker_axes),
                  pack_axes_tree(specs, lay_m))
            pm_flat = make_packed_mean_flat(mesh, layout.worker_axes)
            if run.local_sgd.sync_coalesce:
                # one payload gather per dtype, not per sharding class
                # (executed by the plan's coalesced collective stages)
                pm_coal = make_packed_mean_coalesced(mesh, layout.worker_axes)

    # Resident bucket state rides the kernel flag for EVERY layout:
    # within-worker-sharded leaves live in their own sharded sub-bucket
    # instead of falling back to the tree-in/tree-out kernel path.
    from repro.core.local_sgd import resident_eligible
    resident = resident_eligible(use_kernel, True)
    # Telemetry + controller (ISSUE 3): collect round stats whenever the
    # configured controller needs them; speculative compression-error
    # measurement only for the escalating policies (they decide when to
    # START compressing from the would-be sign error).
    cc = run.controller
    telemetry = cc.wants_telemetry
    init, local_step, sync = make_local_sgd(run, loss, num_workers=num_workers,
                                            wd_mask=wd_mask, use_kernel=use_kernel,
                                            packed_mean_fn=pm,
                                            packed_mean_flat_fn=pm_flat,
                                            packed_mean_coalesced_fn=pm_coal,
                                            bucketable=bucketable,
                                            shard_classes=shard_cls,
                                            resident=resident,
                                            sharded=mesh is not None,
                                            telemetry=telemetry,
                                            speculate_compression=(
                                                cc.wants_speculation))

    n_comp = 1
    blay = None
    if resident:
        blay = flatbuf.build_layout(
            mbase.abstract(specs, jnp.dtype(run.model.param_dtype)),
            wd_mask=wd_mask, shard_classes=shard_cls)
        n_comp = blay.num_buckets
    if worker_set is None:
        from repro.backend.base import WorkerSet
        worker_set = WorkerSet.of(num_workers)
    bundle = TrainBundle(cfg=cfg, run=run, layout=layout, num_workers=num_workers,
                         specs=specs, init=init, local_step=local_step, sync=sync,
                         telemetry=telemetry, n_comp=n_comp, worker_set=worker_set)
    # the bundle's compiled SyncPlan: topology from the config
    # (auto = hierarchical blocks iff block_steps > 1), per-sub-bucket
    # modes from sync_compression, coalesce from sync_coalesce.  fit
    # executes this plan (and lets the controller rewrite it via
    # PlanDelta); the legacy group=/compression= kwargs remain as a
    # per-call shim in core/local_sgd.
    bundle.sync_plan = splan.make_sync_plan(bundle)

    if jit:
        step_kw: dict = {}
        sync_kw: dict = {}
        if mesh is not None:
            sspec = state_partition_specs(specs, layout, run,
                                          resident=resident,
                                          telemetry=telemetry,
                                          bucket_layout=blay,
                                          worker_set=worker_set)
            bspec = inp.train_batch_pspecs(cfg, run.shape, layout)
            ssh = _named(mesh, sspec)
            bsh = _named(mesh, bspec)
            bundle.state_shardings = ssh
            bundle.batch_shardings = bsh
            step_kw = dict(in_shardings=(ssh, bsh, None),
                           out_shardings=(ssh, None))
            sync_kw = dict(in_shardings=(ssh,), out_shardings=ssh)
        # positional adapter for the optional lr_scale arg (pjit with
        # in_shardings rejects kwargs): passing None keeps the original
        # two-arg program; a scalar traces once and serves every value
        jstep = jax.jit(
            lambda s, b, lr_scale: local_step(s, b, lr_scale=lr_scale),
            **step_kw)
        bundle.local_step = (lambda s, b, lr_scale=None:
                             jstep(s, b, lr_scale))
        bundle.step_lower = (lambda s, b, lr_scale=None:
                             jstep.lower(s, b, lr_scale))
        # jit a positional adapter for the static (group, compression,
        # plan, scope) args — SyncPlan is frozen/hashable, so each
        # distinct plan compiles once — and keep the kwarg interface
        # fit expects; the raw jitted object rides along so fit can
        # .lower() the sync for the HLO-measured ledger costs.
        jsync = jax.jit(
            lambda s, group, compression, plan, scope: sync(
                s, group=group, compression=compression, plan=plan,
                scope=scope),
            static_argnums=(1, 2, 3, 4), **sync_kw)
        bundle.sync = (lambda s, *, group=None, compression=None, plan=None,
                       scope=None: jsync(s, group, compression, plan, scope))
        bundle.sync_lower = (lambda s, *, group=None, compression=None,
                             plan=None, scope=None:
                             jsync.lower(s, group, compression, plan, scope))
    return bundle


@dataclass
class ServeBundle:
    cfg: ModelConfig
    layout: MeshLayout
    specs: Any
    prefill: Callable
    decode_step: Callable
    param_shardings: Any = None
    cache_shardings: Any = None


def build_serve(cfg: ModelConfig, shape: InputShape, *, mesh: Mesh | None = None,
                layout: MeshLayout | None = None, jit: bool = True,
                scan: bool = True) -> ServeBundle:
    if layout is None and mesh is not None:
        axes = tuple(mesh.axis_names)
        layout = (long_context_serve_layout(axes) if shape.seq_len >= 262_144
                  else serve_layout(axes))
    if layout is not None and mesh is not None:
        layout = layout.with_mesh(mesh)
    lay_for_model = layout if mesh is not None else None
    specs = lm.param_specs(cfg)

    def prefill_fn(params, batch):
        return lm.prefill(cfg, params, batch["tokens"], lay=lay_for_model,
                          prefix_embed=batch.get("prefix_embed"),
                          enc_frames=batch.get("frames"), scan=scan)

    def decode_fn(params, batch, cache, cache_len):
        return lm.decode_step(cfg, params, batch["tokens"], cache, cache_len,
                              lay=lay_for_model, scan=scan)

    bundle = ServeBundle(cfg=cfg, layout=layout, specs=specs,
                         prefill=prefill_fn, decode_step=decode_fn)

    if mesh is not None and jit:
        psh = _named(mesh, mbase.partition_specs(specs, layout, stacked=False))
        from repro.launch.inputs import WHISPER_MAX_DECODER
        self_len = (min(WHISPER_MAX_DECODER, shape.seq_len)
                    if cfg.cross_attention else shape.seq_len)
        csh = _named(mesh, lm.cache_partition_specs(
            cfg, layout, shape.global_batch, self_len,
            enc_len=shape.seq_len if cfg.cross_attention else None))
        tsh = _named(mesh, inp.serve_token_pspecs(cfg, shape, layout, prefill=False))
        logits_sh = NamedSharding(mesh, layout.spec(
            "batch", None, "vocab",
            dims=(shape.global_batch, 1, cfg.vocab_size)))
        bundle.param_shardings = psh
        bundle.cache_shardings = csh
        bundle.prefill = jax.jit(prefill_fn, in_shardings=(psh, None),
                                 out_shardings=(logits_sh, csh))
        bundle.decode_step = jax.jit(
            decode_fn, in_shardings=(psh, tsh, csh, None),
            out_shardings=(logits_sh, csh))
    return bundle


def build_engine(cfg: ModelConfig, shape: InputShape, params=None, *,
                 page_size: int = 8, num_pages: int | None = None,
                 prefill_len: int | None = None, eos_id: int | None = None,
                 scan: bool = True, seed: int = 0, tracer=None, metrics=None,
                 jit: bool = True):
    """Continuous-batching serving engine for one host (see
    :mod:`repro.serving.engine`).

    The dynamic-batching counterpart of :func:`build_serve`:
    ``shape.global_batch`` decode slots, ``shape.seq_len`` max sequence
    length, a paged KV pool sized for full occupancy.  ``params=None``
    materializes fresh ones from the config's specs (smoke/bench use).
    """
    from repro.serving.engine import DecodeEngine

    if params is None:
        params = mbase.materialize(lm.param_specs(cfg),
                                   jax.random.PRNGKey(seed))
    return DecodeEngine(cfg, params, max_batch=shape.global_batch,
                        max_len=shape.seq_len, page_size=page_size,
                        num_pages=num_pages, prefill_len=prefill_len,
                        eos_id=eos_id, scan=scan, tracer=tracer,
                        metrics=metrics, jit=jit)
