import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run (deliverable e).

Lowers + compiles every (architecture x input shape) on the production
meshes — 16x16 single-pod and 2x16x16 multi-pod — and records
memory_analysis / cost_analysis / parsed collective bytes as JSON under
``experiments/dryrun/``. Failures here are sharding bugs by definition.

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--single-pod]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig, RunConfig
from repro.core.local_sgd import LocalSGDState
from repro.launch import inputs as inp
from repro.launch import steps
from repro.launch.mesh import make_production_mesh
from repro.models import base as mbase
from repro.models import lm
from repro.roofline.hlo import parse_collectives
from repro.sharding.layout import (choose_worker_axes, fsdp_within_worker_layout,
                                   train_layout)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mem_fields(compiled):
    ma = compiled.memory_analysis()
    return {k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")}


def _cost_fields(compiled):
    ca = compiled.cost_analysis() or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def _collectives(compiled, pod_size: int):
    s = parse_collectives(compiled.as_text(), pod_size=pod_size)
    return {"count": s.count(),
            "moved_bytes": s.total_bytes(),
            "moved_bytes_cross_pod": s.total_bytes(cross_pod=True),
            "by_op": s.by_op()}


def _report(name, lowered, compiled, pod_size):
    return {"name": name, **_mem_fields(compiled), **_cost_fields(compiled),
            "collectives": _collectives(compiled, pod_size)}


def pick_train_layout(mesh, cfg: ModelConfig, kind: str = "tp"):
    n_params = mbase.count_params(lm.param_specs(cfg))
    worker_axes, fsdp_axes = choose_worker_axes(mesh, n_params)
    if kind == "fsdp":
        lay = fsdp_within_worker_layout(tuple(mesh.axis_names),
                                        worker_axes=worker_axes)
    else:
        lay = train_layout(tuple(mesh.axis_names), worker_axes=worker_axes,
                           fsdp_axes=fsdp_axes)
    return lay, n_params


def dryrun_train(arch: str, shape: InputShape, mesh, layout_kind="tp") -> dict:
    cfg = configs.get(arch)
    run = RunConfig(model=cfg, shape=shape)
    lay, n_params = pick_train_layout(mesh, cfg, layout_kind)
    lay.validate(mesh)
    W = lay.num_workers(mesh)
    # global batch must split across workers; W=1 degenerates to mini-batch SGD
    bundle = steps.build_train(run, mesh=mesh, layout=lay, num_workers=max(W, 1))

    dtype = jnp.bfloat16
    params = mbase.abstract(bundle.specs, dtype, stacked=max(W, 1))
    state = LocalSGDState(
        params=params, momentum=params, anchor=None, global_u=None,
        ef_memory=None, step=jax.ShapeDtypeStruct((), jnp.int32),
        rng=jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    batch = inp.train_input_specs(cfg, shape, max(W, 1), act_dtype=dtype)

    pod = mesh.shape.get("pod", 0) and mesh.devices.size // mesh.shape["pod"]
    with mesh:
        t0 = time.time()
        lowered = bundle.local_step.lower(state, batch)
        compiled = lowered.compile()
        rep_local = _report("local_step", lowered, compiled, pod)
        rep_local["compile_s"] = round(time.time() - t0, 1)

        t0 = time.time()
        lowered_s = bundle.sync_lower(state)
        compiled_s = lowered_s.compile()
        rep_sync = _report("sync", lowered_s, compiled_s, pod)
        rep_sync["compile_s"] = round(time.time() - t0, 1)

    return {"arch": arch, "shape": shape.name, "kind": "train",
            "mesh": dict(mesh.shape), "num_workers": W, "layout": layout_kind,
            "worker_axes": list(lay.worker_axes), "n_params": n_params,
            "local_step": rep_local, "sync": rep_sync}


def dryrun_serve(arch: str, shape: InputShape, mesh) -> dict:
    cfg = configs.get(arch)
    n_params = mbase.count_params(lm.param_specs(cfg))
    bundle = steps.build_serve(cfg, shape, mesh=mesh)
    dtype = jnp.bfloat16
    params = mbase.abstract(bundle.specs, dtype)
    pod = mesh.shape.get("pod", 0) and mesh.devices.size // mesh.shape["pod"]

    reports = {}
    with mesh:
        if shape.kind == "prefill":
            batch = inp.serve_token_specs(cfg, shape, prefill=True)
            t0 = time.time()
            lowered = bundle.prefill.lower(params, batch)
            compiled = lowered.compile()
            reports["prefill"] = _report("prefill", lowered, compiled, pod)
            reports["prefill"]["compile_s"] = round(time.time() - t0, 1)
        else:
            batch = inp.serve_token_specs(cfg, shape, prefill=False)
            enc_len = shape.seq_len if cfg.cross_attention else None
            self_len = (min(inp.WHISPER_MAX_DECODER, shape.seq_len)
                        if cfg.cross_attention else shape.seq_len)
            cache = jax.eval_shape(
                lambda: lm.init_cache(cfg, shape.global_batch, self_len,
                                      dtype=dtype, enc_len=enc_len))
            t0 = time.time()
            lowered = bundle.decode_step.lower(params, batch, cache,
                                               jnp.int32(self_len))
            compiled = lowered.compile()
            reports["decode"] = _report("decode_step", lowered, compiled, pod)
            reports["decode"]["compile_s"] = round(time.time() - t0, 1)

    return {"arch": arch, "shape": shape.name, "kind": shape.kind,
            "mesh": dict(mesh.shape), "n_params": n_params, **reports}


def dryrun_pair(arch: str, shape_name: str, *, multi_pod: bool,
                layout_kind: str = "tp") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        return dryrun_train(arch, shape, mesh, layout_kind)
    return dryrun_serve(arch, shape, mesh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--layout", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    pairs = (configs.runnable_pairs() if args.all
             else [(args.arch, args.shape)])
    os.makedirs(args.out, exist_ok=True)
    mesh_tag = ("2x16x16" if args.multi_pod else "16x16") + (
        "" if args.layout == "tp" else f"_{args.layout}")
    failures = 0
    for arch, shape in pairs:
        tag = f"{arch}__{shape}__{mesh_tag}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip] {tag} (exists)")
            continue
        print(f"[dryrun] {tag} ...", flush=True)
        t0 = time.time()
        try:
            rep = dryrun_pair(arch, shape, multi_pod=args.multi_pod,
                              layout_kind=args.layout)
            rep["wall_s"] = round(time.time() - t0, 1)
            with open(path, "w") as f:
                json.dump(rep, f, indent=1)
            step_key = ("local_step" if "local_step" in rep
                        else ("prefill" if "prefill" in rep else "decode"))
            r = rep[step_key]
            print(f"  ok {rep['wall_s']}s flops={r['flops']:.3e} "
                  f"temp={r['temp_size_in_bytes']/1e9:.2f}GB "
                  f"coll={r['collectives']['moved_bytes']/1e6:.1f}MB")
        except Exception:
            failures += 1
            print(f"  FAIL {tag}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run failures")
    print("all dry runs passed")


if __name__ == "__main__":
    main()
