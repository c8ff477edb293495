"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the
trace holds one plane per chip (``/device:TPU:<n>``) with a line of
XLA module executions (``XLA Modules``) and a line of the operations
inside them (``XLA Ops``), and host planes whose lines carry the
``TraceAnnotation`` spans: the harness's window (``WINDOW``) and the
program's own ``round`` / ``local_steps`` / ``sync`` spans, which
``fit`` annotates when its tracer has ``annotate=True``.

From these:

- busy time per chip: the union of the intervals of the operations
  the TensorCore runs (``XLA Ops``; asynchronous copies in flight do
  not count) inside the window, and the idle share 1 - busy / window;
- device time per program, keyed by the host span that launched it:
  inside the window every ``local_steps`` and ``sync`` span launches
  exactly one program, so the window's last executions in launch order
  (their ``run_id``) pair one to one with those spans in time order.
  Start times would not do: the device may still run one round's sync
  when the host has already launched the next step;
- the operations that took most device time;
- the longest idle gaps, each labelled with the innermost host span it
  falls in.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench_window"
LAUNCH_SPANS = ("local_steps", "sync")
LABEL_SPANS = ("round", "local_steps", "sync", WINDOW)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# control-flow ops span the ops of their bodies, which the trace lists too
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
TOP = 10


def op_name(text: str) -> str:
    """An XLA op event's instruction name: ``%fusion.12 = (...) ...`` ->
    ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _run_id(ev, default: int) -> int:
    """The launch sequence number of a module execution; its start time
    where the trace gives none."""
    for k, v in ev.stats:
        if k == "run_id":
            return int(v)
    return default


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Sorted, merged (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclass
class Chip:
    ops: list = field(default_factory=list)       # (start, end, name)
    modules: list = field(default_factory=list)   # (start, end, name, run)


@dataclass
class Reduced:
    window: tuple                  # (start_ns, end_ns) of WINDOW
    chips: list                    # Chip per device plane, in device order
    spans: list                    # (start_ns, end_ns, name), host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, chip: Chip) -> list:
        w0, w1 = self.window
        return _union((max(s, w0), min(e, w1)) for s, e, *_ in
                      (chip.ops or chip.modules) if e > w0 and s < w1)

    @property
    def idle_share(self) -> list:
        span = self.window[1] - self.window[0]
        if span <= 0:
            return []
        return [1.0 - sum(e - s for s, e in self._busy(c)) / span
                for c in self.chips]

    @property
    def busy_s(self) -> float:
        if not self.chips:
            return 0.0
        return sum(sum(e - s for s, e in self._busy(c))
                   for c in self.chips) * 1e-9 / len(self.chips)

    def program_s(self, span_name: str) -> tuple[float, float]:
        """(device seconds, executions) of the programs launched from
        ``span_name`` spans inside the window, each a mean over chips."""
        w0, w1 = self.window
        kinds = [n for s, _, n in self.spans
                 if n in LAUNCH_SPANS and w0 <= s <= w1]
        total = count = 0
        for chip in self.chips:
            runs = sorted((r, s, e) for s, e, _, r in chip.modules
                          if w0 <= s <= w1)
            for kind, (_, s, e) in zip(reversed(kinds), reversed(runs)):
                if kind == span_name:
                    total += e - s
                    count += 1
        n = max(len(self.chips), 1)
        return total * 1e-9 / n, count / n

    def top_ops(self, k: int = TOP) -> list:
        w0, w1 = self.window
        per = defaultdict(int)
        for chip in self.chips:
            for s, e, name in chip.ops:
                if s >= w0 and e <= w1 and not CONTAINER.match(name):
                    per[name] += e - s
        n = max(len(self.chips), 1)
        top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / n] for name, ns in top]

    def idle_gaps(self, k: int = TOP) -> list:
        """The ``k`` longest gaps on any chip, labelled by the innermost
        host span around their midpoint."""
        w0, w1 = self.window
        gaps = []
        for chip in self.chips:
            busy = self._busy(chip)
            edges = [w0] + [x for b in busy for x in b] + [w1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, s, e))
        gaps.sort(reverse=True)
        out = []
        for dur, s, e in gaps[:k]:
            mid = (s + e) / 2
            inside = [(se - ss, n) for ss, se, n in self.spans
                      if n in LABEL_SPANS and ss <= mid <= se]
            label = min(inside)[1] if inside else "outside the window"
            if label == WINDOW:
                label = "fit, between spans"
            out.append([label, dur * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def reduce(path: str, *, chips: int) -> Reduced:
    """Reduce the trace at ``path`` for the first ``chips`` TPU chips."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[int, Chip] = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = devices.setdefault(int(m.group(1)), Chip())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        chip.ops.append((s, s + int(ev.duration_ns),
                                         op_name(ev.name)))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        chip.modules.append((s, s + int(ev.duration_ns),
                                             ev.name, _run_id(ev, s)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in LABEL_SPANS:
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    order = sorted(devices)[:chips]
    return Reduced(window=max(windows, key=lambda w: w[1] - w[0]),
                   chips=[devices[i] for i in order], spans=sorted(spans))


@dataclass
class Window:
    """What a per-layer metric reader gets: the reduced trace, the
    program's host spans, and the window's shape."""
    trace: Reduced
    spans: list
    rounds: int
    local_steps: int
    tokens: int
    wall_s: float
    chips: int
    peak: dict
    flops_per_token: float
