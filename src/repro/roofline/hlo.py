"""HLO collective parsing.

``compiled.as_text()`` of an SPMD-partitioned module has per-device
shapes; we extract every collective op, its payload bytes, replica-group
size, and whether the group crosses the pod boundary (ICI vs inter-pod),
then apply standard ring-algorithm per-device byte costs:

    all-reduce          2 (N-1)/N * bytes
    all-gather            (N-1)/N * bytes      (result = gathered shape)
    reduce-scatter        (N-1)   * bytes      (result = shard shape)
    all-to-all            (N-1)/N * bytes
    collective-permute              bytes

NOTE: collectives inside ``while`` bodies (lax.scan) appear ONCE in the
text; the roofline therefore measures small *unrolled* probe modules and
scales by trip count (see analysis.py). The full dry-run parse is
reported raw for the sync/step-level collectives which live outside
scans.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """bytes of 'f32[4,8]{1,0}' or tuple '(f32[4], bf16[2,2])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1
        total += n * _DTYPE_BYTES[dt]
    return total


def _parse_replica_groups(line: str):
    """Return list-of-groups (lists of device ids) or None."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", line)
    if m:
        g, n = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims)))
        if m.group(4):
            perm = [int(d) for d in m.group(4).split(",")]
            ids = ids.reshape(dims).transpose(perm).reshape(-1)
        return ids.reshape(g, n).tolist()
    m = re.search(r"replica_groups=\{(.+?)\}\s*(?:,|$)", line)
    if m:
        body = m.group(1)
        groups = re.findall(r"\{([\d,]+)\}", "{" + body + "}")
        if groups:
            return [[int(x) for x in g.split(",")] for g in groups]
    return None


@dataclass
class CollectiveOp:
    op: str
    result_bytes: int
    group_size: int
    crosses_pod: bool
    moved_bytes: float   # ring-cost per-device bytes


@dataclass
class CollectiveSummary:
    ops: list = field(default_factory=list)

    def total_bytes(self, *, cross_pod: bool | None = None) -> float:
        return float(sum(o.moved_bytes for o in self.ops
                         if cross_pod is None or o.crosses_pod == cross_pod))

    def by_op(self) -> dict:
        out: dict[str, float] = {}
        for o in self.ops:
            out[o.op] = out.get(o.op, 0.0) + o.moved_bytes
        return out

    def count(self) -> int:
        return len(self.ops)


def _ring_bytes(op: str, result_bytes: int, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n * result_bytes
    if op == "all-gather":
        return (n - 1) / n * result_bytes
    if op == "reduce-scatter":
        return float(n - 1) * result_bytes
    if op == "all-to-all":
        return (n - 1) / n * result_bytes
    return float(result_bytes)  # collective-permute


# ---------------------------------------------------------------------------
# Jaxpr op census (pre-XLA, so nothing is fused away or re-materialized)
# ---------------------------------------------------------------------------

def _subjaxprs(v):
    """Yield any (Closed)Jaxpr objects hiding in an eqn param value."""
    if isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)
    elif hasattr(v, "eqns"):              # raw Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):             # ClosedJaxpr
        yield v.jaxpr


def jaxpr_op_counts(jaxpr, *, opaque=("pallas_call",)) -> dict:
    """Count primitive occurrences in a (closed) jaxpr, recursively.

    Descends into call/control-flow sub-jaxprs (pjit, scan, cond,
    custom_*), but treats the primitives in ``opaque`` — kernels — as
    leaves, so e.g. the interpret-mode lowering of a ``pallas_call``
    never pollutes the count.  Used by the resident-state regression
    tests: `flatbuf.flatten` (pack) shows up as ``concatenate`` +
    ``pad`` eqns and `unflatten` as ``slice``/``gather`` (the vmapped
    form), so "zero pack/unpack between syncs" is checkable as
    ``counts.get('concatenate', 0) == 0`` while
    ``counts['pallas_call']`` gives optimizer kernel launches per step.
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    counts: dict[str, int] = {}

    def visit(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            counts[name] = counts.get(name, 0) + 1
            if name in opaque:
                continue
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    visit(sub)

    visit(jaxpr)
    return counts


def parse_collectives(hlo_text: str, *, pod_size: int = 0) -> CollectiveSummary:
    summary = CollectiveSummary()
    # a tuple result may hold parenthesized TPU tilings, e.g.
    # (f32[]{:T(128)}, f32[]{:T(128)}): allow one nesting level
    pat = re.compile(
        r"=\s*(\((?:[^()]|\([^()]*\))*\)|\S+)\s+("
        + "|".join(COLLECTIVES) + r")(-start)?\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m:
            continue
        if f"{m.group(2)}-done" in line:
            continue
        shape_str, op = m.group(1), m.group(2)
        rb = _shape_bytes(shape_str)
        groups = _parse_replica_groups(line)
        n = len(groups[0]) if groups else 1
        crosses = False
        if groups and pod_size:
            g0 = groups[0]
            crosses = len({d // pod_size for d in g0}) > 1
        summary.ops.append(CollectiveOp(op=op, result_bytes=rb, group_size=n,
                                        crosses_pod=crosses,
                                        moved_bytes=_ring_bytes(op, rb, n)))
    return summary
