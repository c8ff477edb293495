"""Drive the system under test: local-SGD training through ``fit``.

The window runs ``repro.launch.train.fit`` on a ``LocalBackend`` with the
tree path (``use_kernel=False``), as the training CLI does: W workers
vmapped on one chip, or one worker per chip on a ("data", "model") =
(W, 1) mesh. Everything here is built from the cell's configuration and
traffic files.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference

# configuration-file key -> the program's ModelConfig field
MODEL_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "param_dtype": "param_dtype",
}


def model_config(cfg: dict, seq_len: int):
    """The program's ModelConfig for a configuration file: a plain
    attention + SwiGLU decoder with RoPE over the whole head.

    The program stacks the layers of each whole period of its block
    pattern over a leading axis and keeps the remaining layers apart;
    with ``stacked_layers`` false the period is longer than the model,
    so every layer is kept apart."""
    from repro.configs.base import BlockDef, ModelConfig

    if cfg["hidden_act"] != "silu" or cfg.get("rope_scaling") is not None:
        raise ValueError(f"{cfg['name']}: the program runs SwiGLU with "
                         "unscaled RoPE over the whole head only")
    period = 1 if cfg["stacked_layers"] else cfg["num_hidden_layers"] + 1
    return ModelConfig(
        name=cfg["name"], family="dense", citation=cfg["source"],
        blocks=(BlockDef("attn", "swiglu"),) * period, max_seq_len=seq_len,
        **{MODEL_KEYS[k]: cfg[k] for k in MODEL_KEYS})


def run_config(mc, traffic: dict):
    from repro.configs.base import (InputShape, LocalSGDConfig, OptimConfig,
                                    RunConfig)
    W, B, S = traffic["workers"], traffic["local_batch"], traffic["seq_len"]
    o = traffic["optim"]
    shape = InputShape("bench", S, W * B, "train")
    return RunConfig(
        model=mc, shape=shape,
        local_sgd=LocalSGDConfig(local_steps=traffic["local_steps"],
                                 local_momentum=o["momentum"],
                                 nesterov=o["nesterov"]),
        optim=OptimConfig(base_lr=o["base_lr"],
                          base_batch=o.get("base_batch", W * B),
                          weight_decay=o["weight_decay"],
                          grad_clip=o["grad_clip"]),
        steps=traffic["local_steps"])


def _where(path) -> tuple[str, object]:
    """(weight name, layer) of a leaf of the program's parameter tree:
    layer None outside the layers, "all" for a weight stacked over
    layers, else the index of the layer kept apart."""
    keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    layer = {"layers": "all", "rem": keys[1] if len(keys) > 1 else None}
    return str(keys[-1]), layer.get(keys[0])


@partial(jax.jit, static_argnums=0)
def _worker_norms(where, xs, base):
    out = []
    for (name, layer), x in zip(where, xs):
        if base is not None:
            b = base[name]
            b = b[layer] if isinstance(layer, int) else b
            x = x.astype(jnp.float32) - b.astype(jnp.float32)[None]
        out.append(reference.norm(x, 2 if layer == "all" else 1))
    return out


def worker_leaf_norms(tree, base=None) -> dict:
    """Per-worker norm of every weight of the program's (W, ...)-stacked
    parameter tree, per layer for layer weights, keyed as the reference
    keys them: name -> (W,) or (W, layers).  With ``base`` (weights
    keyed as the reference's), of the change ``tree - base``."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    where = tuple(_where(p) for p, _ in leaves)
    norms = _worker_norms(where, [x for _, x in leaves], base)
    by_name: dict = {}
    for (name, layer), n in zip(where, norms):
        by_name.setdefault(name, []).append((layer, np.asarray(n)))
    out = {}
    for name, parts in by_name.items():
        if parts[0][0] in (None, "all"):
            out[name] = parts[0][1]
        else:
            parts.sort(key=lambda part: part[0])
            out[name] = np.stack([n for _, n in parts], axis=1)
    return out


class Trainer:
    """One cell's compiled program: the backend, the bundle (compiled
    local step and sync) and the data feed, built once; every ``fit``
    call of set-up, window and trace goes through this same object."""

    def __init__(self, cfg: dict, traffic: dict, devices, tokens):
        from jax.sharding import Mesh

        from repro.backend.local import LocalBackend

        self.traffic = traffic
        W = traffic["workers"]
        self.run = run_config(model_config(cfg, traffic["seq_len"]), traffic)
        mesh = None
        if traffic["placement"] == "mesh":
            mesh = Mesh(np.array(devices[:W]).reshape(W, 1),
                        ("data", "model"))
        elif traffic["placement"] != "vmap":
            raise ValueError(f"placement {traffic['placement']!r}")
        self.mesh = mesh
        self.backend = LocalBackend(W, mesh=mesh)
        self.bundle = self.backend.build(self.run)
        # fit prices each sync round from a fresh AOT compile of the sync
        # program, once per fit call; the window must not compile, so the
        # ledger keeps the plan's ring-model estimates instead
        self.bundle.sync_lower = None
        self.data = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}

    def replicate(self, tree):
        """``tree`` where the program's state can meet it: replicated over
        the mesh, or as it is on one chip."""
        if self.mesh is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(tree, NamedSharding(self.mesh, PartitionSpec()))

    @property
    def tokens_per_step(self) -> int:
        t = self.traffic
        return t["workers"] * t["local_batch"] * t["seq_len"]

    def fit(self, num_steps: int, *, seed: int, tracer=None):
        """One ``fit`` from ``seed`` over a fresh feed; returns (state,
        history, wall seconds up to the state being ready)."""
        from repro.data.partition import ShardedBatches
        from repro.launch.train import fit

        t = self.traffic
        feed = ShardedBatches(self.data, t["workers"], t["local_batch"],
                              seed=seed)
        t0 = time.perf_counter()
        state, hist, _ = fit(self.run, feed, bundle=self.bundle,
                             backend=self.backend, num_steps=num_steps,
                             seed=seed, log=lambda *a: None, tracer=tracer)
        jax.block_until_ready(state)
        return state, hist, time.perf_counter() - t0

    def checked_steps(self, seed: int, steps: int, initial) -> dict:
        """The readings the reference is compared with, from the first
        ``steps`` steps: per-step losses, each worker's momentum norms
        after step 1 and parameter-change norms after ``steps``, against
        the weights ``initial()`` makes (called once the fits are done,
        so that they are not held through them).  These fits also
        compile every program the window runs."""
        state, hist, _ = self.fit(1, seed=seed)
        grad = worker_leaf_norms(state.momentum)
        del state
        state, hist, _ = self.fit(steps, seed=seed)
        params = state.params
        del state
        change = worker_leaf_norms(params, self.replicate(initial()))
        del params
        return {"losses": [h["loss"] for h in hist], "grad_norms": grad,
                "change_norms": change}

    def round_seconds(self, seed: int) -> tuple[float, float]:
        """(seconds of one warm round, seconds of a fit call's own set-up
        around it), from one fit of one round and its ``round`` span."""
        from repro import telemetry as tele

        tr = tele.Tracer()
        state, _, wall = self.fit(self.traffic["local_steps"], seed=seed,
                                  tracer=tr)
        del state
        rnd = next(s.dur_s for s in tr.spans if s.name == "round")
        return rnd, max(wall - rnd, 0.0)


def rounds_for(seconds: float, round_s: float, extra_s: float) -> int:
    """Whole rounds that, with a fit call's own set-up, fill ``seconds``."""
    return max(1, math.ceil((seconds - extra_s) / round_s))
