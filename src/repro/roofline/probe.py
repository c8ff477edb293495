import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Per-layer collective probe (the §Perf measurement instrument).

Collectives inside lax.scan bodies are only printed once in HLO text, so
the full dry-run parse under-counts per-layer collectives. This probe
lowers the SAME train step with 1 and 2 *unrolled* layer-periods, parses
both, and linearly extrapolates:

    coll(L) = fixed + slope * (L / period)

Layer-boundary collectives (Megatron TP all-reduces, FSDP weight
all-gathers / grad reduce-scatters) all sit outside the attention/loss
inner scans, so the slope is exact for them.

    PYTHONPATH=src python -m repro.roofline.probe --arch qwen3-32b \
        --shape train_4k --layout fsdp
"""
import argparse
import json

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import INPUT_SHAPES, RunConfig
from repro.core.local_sgd import LocalSGDState, make_local_sgd
from repro.launch import inputs as inp
from repro.launch.dryrun import pick_train_layout
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import state_partition_specs, _named
from repro.models import base as mbase
from repro.models import lm
from repro.roofline.hlo import parse_collectives


def _measure(cfg, shape, mesh, lay, W):
    run = RunConfig(model=cfg, shape=shape)
    specs = lm.param_specs(cfg)
    wd_mask = mbase.norm_param_mask(specs)
    lay_m = lay.with_mesh(mesh)

    def loss(params, batch):
        return lm.loss_fn(cfg, params, batch, lay=lay_m, scan=False,
                          remat=run.remat)

    init, local_step, sync = make_local_sgd(run, loss, num_workers=W,
                                            wd_mask=wd_mask)
    ssh = _named(mesh, state_partition_specs(specs, lay_m, run))
    bsh = _named(mesh, inp.train_batch_pspecs(cfg, shape, lay_m))
    step = jax.jit(local_step, in_shardings=(ssh, bsh), out_shardings=(ssh, None))

    dtype = jnp.bfloat16
    params = mbase.abstract(specs, dtype, stacked=W)
    state = LocalSGDState(params=params, momentum=params, anchor=None,
                          global_u=None, ef_memory=None,
                          step=jax.ShapeDtypeStruct((), jnp.int32),
                          rng=jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    batch = inp.train_input_specs(cfg, shape, W, act_dtype=dtype)
    with mesh:
        compiled = step.lower(state, batch).compile()
    s = parse_collectives(compiled.as_text(),
                          pod_size=(mesh.devices.size // mesh.shape["pod"]
                                    if "pod" in mesh.axis_names else 0))
    ca = compiled.cost_analysis() or {}
    return {"coll_bytes": s.total_bytes(), "coll_by_op": s.by_op(),
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def probe_train(arch: str, shape_name: str, layout_kind: str = "tp"):
    mesh = make_production_mesh()
    cfg_full = configs.get(arch)
    shape = INPUT_SHAPES[shape_name]
    period = len(cfg_full.blocks)
    lay, _ = pick_train_layout(mesh, cfg_full, layout_kind)
    W = max(lay.num_workers(mesh), 1)

    m1 = _measure(cfg_full.replace(num_layers=period), shape, mesh, lay, W)
    m2 = _measure(cfg_full.replace(num_layers=2 * period), shape, mesh, lay, W)

    n_units = cfg_full.num_layers / period
    out = {"arch": arch, "shape": shape_name, "layout": layout_kind,
           "workers": W, "period": period}
    for key in ("coll_bytes", "flops", "bytes"):
        slope = m2[key] - m1[key]
        fixed = m1[key] - slope
        out[f"{key}_per_period"] = slope
        out[f"{key}_fixed"] = fixed
        out[f"{key}_full"] = fixed + slope * n_units
    out["probe1"] = m1
    out["probe2"] = m2
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--layout", default="tp")
    args = ap.parse_args()
    out = probe_train(args.arch, args.shape, args.layout)
    print(json.dumps({k: v for k, v in out.items()
                      if not k.startswith("probe")}, indent=1))
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "experiments", "probes")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, f"{args.arch}__{args.shape}__{args.layout}.json"),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
