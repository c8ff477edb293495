import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Sync-step collective probe: measure the wire cost of the paper's
synchronization variants (Alg. 1 plain averaging, Alg. 3 signSGD, and
the 1-bit packed wire format) by lowering `sync` and parsing collectives.

    PYTHONPATH=src python -m repro.roofline.sync_probe --arch deepseek-v2-lite-16b
"""
import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import INPUT_SHAPES, LocalSGDConfig, RunConfig
from repro.core.local_sgd import LocalSGDState, make_local_sgd
from repro.launch.dryrun import pick_train_layout
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import state_partition_specs, _named
from repro.models import base as mbase
from repro.models import lm
from repro.roofline.hlo import parse_collectives


def measure_sync(arch: str, *, compression: str, wire_pack: bool,
                 bucket_sync: bool = True, shape_name: str = "train_4k"):
    mesh = make_production_mesh()
    cfg = configs.get(arch)
    shape = INPUT_SHAPES[shape_name]
    lay, _ = pick_train_layout(mesh, cfg)
    lay_m = lay.with_mesh(mesh)
    W = max(lay.num_workers(mesh), 1)
    ls = LocalSGDConfig(local_steps=8, sync_compression=compression,
                        wire_pack=wire_pack)
    run = RunConfig(model=cfg, shape=shape, local_sgd=ls)
    specs = lm.param_specs(cfg)

    def loss(p, b):  # sync never traces the loss
        raise NotImplementedError

    from repro.core import flatbuf
    from repro.core.local_sgd import (make_packed_mean, make_packed_mean_flat,
                                      pack_axes_tree)
    pm = ((make_packed_mean(mesh, lay.worker_axes),
           pack_axes_tree(specs, lay_m)) if wire_pack else None)
    pm_flat = (make_packed_mean_flat(mesh, lay.worker_axes)
               if wire_pack and bucket_sync else None)
    cls = flatbuf.shard_classes(specs, lay_m)
    init, local_step, sync = make_local_sgd(
        run, loss, num_workers=W, packed_mean_fn=pm,
        packed_mean_flat_fn=pm_flat, bucket_sync=bucket_sync,
        bucketable=flatbuf.replicated_tree(cls), shard_classes=cls)
    ssh = _named(mesh, state_partition_specs(specs, lay_m, run))
    jsync = jax.jit(sync, static_argnames=("group",),
                    in_shardings=(ssh,), out_shardings=ssh)

    dtype = jnp.bfloat16
    stacked = mbase.abstract(specs, dtype, stacked=W)
    single = mbase.abstract(specs, dtype)
    state = LocalSGDState(
        params=stacked, momentum=stacked,
        anchor=single if compression != "none" else None,
        global_u=None,
        ef_memory=stacked if compression == "ef_sign" else None,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        rng=jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    with mesh:
        compiled = jsync.lower(state).compile()
    s = parse_collectives(compiled.as_text())
    return {"arch": arch, "compression": compression, "wire_pack": wire_pack,
            "bucket_sync": bucket_sync, "workers": W,
            "coll_bytes": s.total_bytes(), "by_op": s.by_op(),
            "count": s.count()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-v2-lite-16b")
    args = ap.parse_args()
    results = []
    # bucket_sync=False rows expose the per-leaf dispatch tax the flat
    # parameter bus removes (one collective per dtype bucket)
    for compression, pack, bucket in [("none", False, False),
                                      ("none", False, True),
                                      ("sign", False, True),
                                      ("sign", True, False),
                                      ("sign", True, True)]:
        r = measure_sync(args.arch, compression=compression, wire_pack=pack,
                         bucket_sync=bucket)
        results.append(r)
        print(json.dumps(r))
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "experiments", "probes",
                        f"sync__{args.arch}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
