"""Mesh layouts: map logical tensor axes onto mesh axes.

The central object is :class:`MeshLayout`:

* ``worker_axes`` — the mesh axes that enumerate local-SGD workers. The
  product of their sizes is ``K`` (the paper's number of workers). During
  the local phase each worker owns an independent parameter copy: every
  parameter is stacked with a leading ``W`` dim sharded over
  ``worker_axes``, so GSPMD emits *zero* collectives across them.
* ``rules`` — logical-axis name -> mesh axis (or tuple, or None) for
  everything *within* a worker (tensor parallelism, within-worker FSDP,
  batch sharding for inference).

Model code only ever names logical axes; layouts decide placement.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = None | str | tuple[str, ...]

# Logical axes used across the model zoo.
LOGICAL_AXES = (
    "batch",        # per-worker batch (train) / global batch (serve)
    "seq",          # sequence (activations)
    "kv_seq",       # KV-cache sequence dim (may be sharded for long ctx)
    "embed",        # d_model
    "heads",        # attention query heads
    "kv_heads",     # attention kv heads
    "mlp",          # FFN hidden
    "vocab",        # vocabulary
    "experts",      # MoE experts
    "expert_mlp",   # MoE expert hidden
    "ssm_inner",    # SSM inner channels / mLSTM heads dim
    "layers",       # stacked scan-over-layers dim (never sharded)
)


@dataclass(frozen=True)
class MeshLayout:
    mesh_axes: tuple[str, ...]
    worker_axes: tuple[str, ...]
    rules: dict[str, AxisVal] = field(default_factory=dict)
    # mesh axis sizes; when set, spec() drops rules that do not divide the
    # concrete dim (e.g. kv_heads=1 cannot shard over a 16-way model axis)
    sizes: dict[str, int] = field(default_factory=dict)
    # the mesh this layout was bound to by with_mesh(): sharding
    # constraints name it explicitly, so no mesh context is needed
    mesh: Mesh | None = field(default=None, compare=False, repr=False)

    def rule(self, name: str) -> AxisVal:
        return self.rules.get(name)

    def axis_size(self, v: AxisVal) -> int:
        if v is None:
            return 1
        names = (v,) if isinstance(v, str) else v
        return int(np.prod([self.sizes.get(a, 1) for a in names]))

    def _effective(self, axes, dims, used: set) -> tuple[AxisVal, ...]:
        """Apply the rules to logical ``axes`` exactly as a PartitionSpec
        would be built: shape-aware divisibility drop plus first-wins
        mesh-axis dedup, mutating ``used`` with the axes consumed."""
        out: list[AxisVal] = []
        for i, a in enumerate(axes):
            r = None if a is None else self.rule(a)
            if r is not None and dims is not None and self.sizes:
                if dims[i] % self.axis_size(r) != 0:
                    r = None
            # a mesh axis may appear at most once per spec: first wins
            if r is not None:
                names = (r,) if isinstance(r, str) else r
                if any(nm in used for nm in names):
                    r = None
                else:
                    used.update(names)
            out.append(r)
        return tuple(out)

    def dim_shards(self, axes, dims=None) -> tuple[AxisVal, ...]:
        """Per-dim EFFECTIVE within-worker sharding of a leaf.

        This is the single source of truth shared by :meth:`spec` and
        ``flatbuf.shard_classes``: the rule actually applied to each dim
        (after the shape-aware divisibility drop and first-wins dedup),
        so sub-bucket classification can never disagree with the
        PartitionSpecs the state is placed with."""
        return self._effective(axes, dims, set())

    def spec(self, *axes: str | None, stacked: bool = False,
             dims: tuple[int, ...] | None = None) -> P:
        """PartitionSpec for logical axes. ``stacked`` prepends worker dim.

        ``dims``: concrete dim sizes matching ``axes``; rules that do not
        evenly divide their dim are dropped (shape-aware sharding).
        """
        parts: list[AxisVal] = []
        if stacked:
            parts.append(self.worker_axes if len(self.worker_axes) != 1 else self.worker_axes[0])
        used: set[str] = set()
        for v in parts:
            for nm in ((v,) if isinstance(v, str) else (v or ())):
                used.add(nm)
        parts.extend(self._effective(axes, dims, used))
        return P(*parts)

    def with_mesh(self, mesh: Mesh) -> "MeshLayout":
        """Bind to ``mesh``: record its axis sizes, and drop rule axes the
        mesh does not have (a ``("data",)`` mesh has no tensor-parallel
        axis, so "model" rules become replicated).  Worker axes the
        mesh lacks are an error: they define the workers."""
        names = tuple(mesh.axis_names)
        missing = [a for a in self.worker_axes if a not in names]
        if missing:
            raise ValueError(f"worker axes {missing} of the layout are not "
                             f"axes of the mesh {names}")

        def keep(v: AxisVal) -> AxisVal:
            axes = () if v is None else (v,) if isinstance(v, str) else v
            kept = tuple(a for a in axes if a in names)
            if kept == tuple(axes):
                return v
            return kept[0] if len(kept) == 1 else (kept or None)

        return replace(self, rules={k: keep(v) for k, v in self.rules.items()},
                       sizes={a: int(mesh.shape[a]) for a in names},
                       mesh=mesh)

    def sharding(self, *axes: str | None, dims: tuple[int, ...] | None = None):
        """The sharding constraint for logical ``axes``: a NamedSharding on
        the bound mesh, or the bare PartitionSpec (resolved against the
        context mesh) for a layout that was never bound."""
        spec = self.spec(*axes, dims=dims)
        return spec if self.mesh is None else NamedSharding(self.mesh, spec)

    def num_workers(self, mesh: Mesh) -> int:
        return int(np.prod([mesh.shape[a] for a in self.worker_axes])) if self.worker_axes else 1

    def within_worker_size(self, mesh: Mesh) -> int:
        return mesh.devices.size // max(self.num_workers(mesh), 1)

    def validate(self, mesh: Mesh) -> None:
        for a in self.worker_axes:
            if a not in mesh.axis_names:
                raise ValueError(f"worker axis {a!r} not in mesh {mesh.axis_names}")
        used: list[str] = []
        for v in self.rules.values():
            for a in (v,) if isinstance(v, str) else (v or ()):
                if a not in mesh.axis_names:
                    raise ValueError(f"rule axis {a!r} not in mesh {mesh.axis_names}")
                used.append(a)
        overlap = set(used) & set(self.worker_axes)
        if overlap:
            raise ValueError(
                f"mesh axes {sorted(overlap)} are both worker axes and within-worker "
                "rule axes; a worker's parameter copy cannot be sharded over the axis "
                "that distinguishes workers"
            )


# ---------------------------------------------------------------------------
# Default layouts
# ---------------------------------------------------------------------------

def train_layout(mesh_axes: tuple[str, ...], *, worker_axes: tuple[str, ...],
                 fsdp_axes: tuple[str, ...] = ()) -> MeshLayout:
    """Training layout: TP over 'model'; optional within-worker FSDP axes.

    FSDP axes shard the *embed* dim of params (gathered on use by GSPMD) and
    the per-worker batch. Worker axes are excluded from all rules.
    """
    tp = "model"
    batch = fsdp_axes or None
    return MeshLayout(
        mesh_axes=mesh_axes,
        worker_axes=worker_axes,
        rules={
            "batch": batch if batch else None,
            "embed": fsdp_axes if fsdp_axes else None,
            "heads": tp,
            "kv_heads": tp,
            "mlp": tp,
            "vocab": tp,
            "experts": tp,
            "expert_mlp": None,
            "ssm_inner": tp,
            "seq": None,
            "kv_seq": None,
        },
    )


def fsdp_within_worker_layout(mesh_axes: tuple[str, ...], *,
                              worker_axes: tuple[str, ...],
                              shard_axes: tuple[str, ...] = ("model",)) -> MeshLayout:
    """ZeRO-3-style within-worker layout (beyond-paper optimization).

    Weights are sharded on their *embed/vocab* dims over ``shard_axes`` and
    gathered on use; the per-worker batch is sharded over the same axes, so
    activations are never replicated. Collective bytes per step scale with
    PARAM bytes (all-gather fwd/bwd + grad reduce-scatter) instead of with
    TOKENS x d_model (Megatron-TP all-reduces) — a large win whenever
    tokens_per_worker * d_model >> params_per_layer (see EXPERIMENTS §Perf).
    """
    fs = shard_axes if len(shard_axes) != 1 else shard_axes[0]
    return MeshLayout(
        mesh_axes=mesh_axes,
        worker_axes=worker_axes,
        rules={
            "batch": fs,
            "embed": fs,
            "vocab": fs,       # head stays output-sharded (dedup drops embed)
            "heads": None,
            "kv_heads": None,
            "mlp": None,
            "experts": fs,
            "expert_mlp": None,
            "ssm_inner": None,
            "seq": None,
            "kv_seq": None,
        },
    )


def serve_layout(mesh_axes: tuple[str, ...], *, shard_cache_seq: bool = False) -> MeshLayout:
    """Inference layout: batch over data(+pod), TP over model, no workers."""
    data_axes = tuple(a for a in mesh_axes if a != "model")
    return MeshLayout(
        mesh_axes=mesh_axes,
        worker_axes=(),
        rules={
            "batch": data_axes,
            "embed": None,
            "heads": "model",
            "kv_heads": "model",
            "mlp": "model",
            "vocab": "model",
            "experts": "model",
            "expert_mlp": None,
            "ssm_inner": "model",
            "seq": None,
            "kv_seq": "model" if shard_cache_seq else None,
        },
    )


def long_context_serve_layout(mesh_axes: tuple[str, ...]) -> MeshLayout:
    """Batch=1 long-context decode: shard KV/cache sequence over everything.

    With batch=1 there is no batch parallelism to exploit; the cache is the
    dominant tensor, so its sequence dim is sharded over data(+pod) and heads
    over model. Softmax over the sharded seq dim makes GSPMD emit the
    distributed-attention all-reduces (max & sum).
    """
    data_axes = tuple(a for a in mesh_axes if a != "model")
    return MeshLayout(
        mesh_axes=mesh_axes,
        worker_axes=(),
        rules={
            "batch": None,
            "embed": None,
            "heads": "model",
            "kv_heads": "model",
            "mlp": "model",
            "vocab": "model",
            "experts": "model",
            "expert_mlp": None,
            "ssm_inner": "model",
            "seq": data_axes,
            "kv_seq": data_axes,
        },
    )


# ---------------------------------------------------------------------------
# Memory model: pick worker granularity per arch (see DESIGN §Arch-applicability)
# ---------------------------------------------------------------------------

def param_bytes_per_chip(num_params: int, *, bytes_per_param: int,
                         chips_per_worker: int) -> float:
    return num_params * bytes_per_param / chips_per_worker


def choose_worker_axes(mesh: Mesh, num_params: int, *,
                       bytes_per_param: int = 6,  # bf16 w + bf16 m + bf16 g
                       hbm_budget: float = 13e9   # 16 GB v5e minus activations
                       ) -> tuple[tuple[str, ...], tuple[str, ...]]:
    """Return (worker_axes, fsdp_axes) — maximize K subject to memory.

    Candidates, most-parallel first (axis names present in the mesh):
      (pod, data) / (data,)  -> workers over all data axes, no FSDP
      (pod,)                 -> one worker per pod, FSDP over data
      ()                     -> degenerate K=1 (== mini-batch SGD), FSDP over all data axes
    """
    names = mesh.axis_names
    data_axes = tuple(a for a in names if a in ("pod", "data"))
    candidates: list[tuple[tuple[str, ...], tuple[str, ...]]] = [(data_axes, ())]
    if "pod" in names:
        candidates.append((("pod",), ("data",)))
    candidates.append(((), data_axes))
    model_size = mesh.shape.get("model", 1)
    for worker_axes, fsdp_axes in candidates:
        chips_per_worker = model_size * int(
            np.prod([mesh.shape[a] for a in fsdp_axes]) if fsdp_axes else 1)
        if param_bytes_per_chip(num_params, bytes_per_param=bytes_per_param,
                                chips_per_worker=chips_per_worker) <= hbm_budget:
            return worker_axes, fsdp_axes
    return candidates[-1]


def shardings(tree_of_specs, mesh: Mesh):
    """Map a pytree of PartitionSpecs to NamedShardings."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_of_specs,
                        is_leaf=lambda x: isinstance(x, P))
