"""Span-based host tracing: the seconds-denominated sensor layer.

The paper's headline claim is about *time-to-accuracy* — local SGD wins
because it trades wall-clock communication for local computation — but
until this module the repo could price a round only in analytic/HLO
*bytes* (``telemetry.ledger``), never in measured *seconds*.  A
:class:`Tracer` records host-side :class:`Span` s around the round loop
(``launch/train.fit``), the sync pipeline (``core/syncplan`` stages) and
the controller decisions, so every quantity the ledger prices in bytes
also gets a wall-clock figure, exported to Perfetto / Prometheus by
:mod:`repro.telemetry.export`.

Span taxonomy (the names ``fit`` and the executors emit — exporters and
the trend tooling key off these):

=================  ========================================================
``round``          one global sync round: H local steps + the global sync
``local_steps``    one ``bundle.local_step`` call (H fused local steps);
                   parent of ``batch`` and ``step_dispatch``
``batch``          drawing (and, with a batch scale, stacking) the next
                   batch of every worker
``step_dispatch``  the ``bundle.local_step`` call itself: argument
                   handling and the launch of the jitted step (attr
                   ``queued``: 1 where the program dispatched before it
                   was still not ready when it began; also on ``sync``)
``sync``           one ``bundle.sync`` call (scope attr: ``block``/``global``)
``collective``     one collective stage of the SyncPlan schedule — carries
                   the SAME ``stage`` id ``CommsLedger.record_plan`` prices,
                   so each stage gets bytes *and* seconds
``ledger``         the round's bookkeeping after a sync: ledger pricing,
                   the on-device stats summary, the worker-time census and
                   the ``RoundReport`` (whose loss read-back waits on the
                   device)
``controller``     one ``update`` + ``plan_delta`` decision, attrs = the
                   emitted PlanDelta + the policy's ``decisions`` provenance
``readback``       a step's metrics read back to the host (``float``),
                   in ``fit`` one step late (attr ``step``: whose): where
                   the host waits for the device to finish that step
``fit_setup``      ``fit`` before its loop: weights made, state initialised,
                   plan and controller set up (attr ``state_leaves``)
``resize``         one elastic worker-set resize (state surgery + rebuild)
``eval``           one ``eval_fn`` call
``checkpoint``     one ``checkpoint_fn`` call
``admit``          serving: one admission wave (queue -> engine slots)
``prefill``        serving: one prompt prefill + page write
``decode``         serving: one continuous-batching decode step
``swap``           serving: one live weight install (hot-swap), attrs carry
                   the installed manifest version
=================  ========================================================

Every span carries an ``id`` and its ``parent``: the innermost span
open on the same thread when it started.  A span's self time is its
duration minus what its children cover (:func:`self_time_s`), so it is
computable from the recorded spans alone.

Compile counters: an enabled :class:`Tracer` counts three JAX events
process-wide (``jax.monitoring`` listeners, registered once per process
by the first enabled tracer) — jaxpr traces (``traces``), backend
compiles, persistent-cache loads among them (``compiles``), and
persistent-cache hits (``cache_loads``).  Each finished span carries the
non-zero counts that happened while it was open as attributes, so a
retrace or a compile shows on the span that paid for it.

Measurement semantics
---------------------

JAX dispatch is asynchronous: without fencing, a span around a jitted
call measures *dispatch* time, with the device work of span *i* possibly
draining inside span *i+1*.  ``Tracer(fence=True)`` turns
``Span.fence(value)`` into ``jax.block_until_ready`` at the span
boundary, so durations become true wall-clock at the cost of breaking
dispatch pipelining (a perturbation — defaults OFF, see README).  The
trajectory itself is never affected either way: tracing is host-side
observation only, and ``fit`` without a tracer runs the exact pre-trace
code path (pinned bitwise by tests/test_trace.py).

``Tracer(annotate=True)`` additionally enters a
``jax.profiler.TraceAnnotation`` for the span's lifetime, so host spans
line up with device traces when a ``jax.profiler.trace`` capture is
running.

Shared clock: a span's ``ts_s`` counts ``time.perf_counter`` seconds
from the tracer's origin; the profiler stamps its host events on a
clock of its own, off by an offset that is constant over a capture.
:func:`clock_offset_ns` recovers that offset by pairing the tracer's
spans with the same annotated spans as the capture holds them, so any
span of the program — including ones a reduction of the capture drops —
can be laid over the device's busy intervals:
``profiled_ns = span.ts_s * 1e9 + offset``.
"""
from __future__ import annotations

import itertools
import statistics
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any

SPAN_NAMES = ("round", "local_steps", "batch", "step_dispatch", "sync",
              "collective", "ledger", "controller", "readback", "fit_setup",
              "resize", "eval", "checkpoint",
              "admit", "prefill", "decode", "swap")

# span name -> Perfetto category (groups the trace viewer's tracks)
SPAN_CATEGORIES = {
    "round": "train", "local_steps": "train", "batch": "train",
    "step_dispatch": "train", "readback": "train", "fit_setup": "train",
    "sync": "sync", "collective": "sync", "ledger": "sync",
    "controller": "control", "resize": "control",
    "eval": "eval", "checkpoint": "checkpoint",
    "admit": "serve", "prefill": "serve", "decode": "serve", "swap": "serve",
}

# jax.monitoring event -> the span attribute that counts it
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
    "/jax/compilation_cache/cache_hits": "cache_loads",
}
JIT_KINDS = tuple(JIT_EVENTS.values())
_jit_counts = dict.fromkeys(JIT_KINDS, 0)
_listening = False
_jit_lock = threading.Lock()


def _count_jit_event(event: str, *args, **kwargs) -> None:
    kind = JIT_EVENTS.get(event)
    if kind is not None:
        with _jit_lock:
            _jit_counts[kind] += 1


def _listen() -> None:
    """Register the compile-counting listeners, once per process."""
    global _listening
    with _jit_lock:
        if _listening:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(_count_jit_event)
        jax.monitoring.register_event_duration_secs_listener(_count_jit_event)
        _listening = True


def jit_events() -> dict:
    """Process-wide counts of JAX traces, compiles and cache loads seen
    since the first enabled tracer registered its listeners."""
    return dict(_jit_counts)


@dataclass
class Span:
    """One traced interval.  ``ts_s`` is seconds since the tracer's
    origin (``time.perf_counter`` based); ``dur_s`` is set on finish
    (None while open / on a disabled tracer).  ``id`` is unique within
    its tracer; ``parent`` is the id of the innermost span open on the
    same thread when this one started (None at top level)."""
    name: str
    ts_s: float = 0.0
    dur_s: float | None = None
    attrs: dict = field(default_factory=dict)
    tid: int = 0
    id: int = 0
    parent: int | None = None
    _tracer: Any = None
    _annotation: Any = None
    _jit0: tuple = ()

    @property
    def cat(self) -> str:
        return SPAN_CATEGORIES.get(self.name, "misc")

    def set(self, **attrs) -> "Span":
        """Attach attributes (exported as Perfetto ``args``)."""
        if self._tracer is not None:
            self.attrs.update(attrs)
        return self

    def fence(self, value):
        """Opt-in async fence: with ``Tracer(fence=True)``, block until
        ``value``'s device computation is done so the span measures real
        wall-clock, not dispatch.  Always returns ``value`` unchanged —
        safe to wrap any jitted result inline."""
        if self._tracer is not None and self._tracer.fence:
            import jax
            jax.block_until_ready(value)
        return value

    # context-manager form: ``with tracer.span("sync") as sp: ...``
    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc):
        if self._tracer is not None:
            self._tracer.finish(self)
        return False


_NULL_SPAN = Span(name="null")          # shared, attr-dropping no-op


class Tracer:
    """Collects :class:`Span` s; thread-safe appends, perf_counter base.

    ``fence``    — make ``Span.fence`` block_until_ready (defaults OFF:
                   fencing perturbs dispatch pipelining).
    ``annotate`` — wrap spans in ``jax.profiler.TraceAnnotation`` so a
                   concurrent device-profiler capture shows them.
    ``metrics``  — optional :class:`~repro.telemetry.metrics.MetricsRegistry`
                   consumers feed alongside the spans (``fit`` does).
    """

    def __init__(self, *, fence: bool = False, annotate: bool = False,
                 metrics=None):
        self.fence = bool(fence)
        self.annotate = bool(annotate)
        self.metrics = metrics
        self.spans: list[Span] = []
        self._origin = time.perf_counter()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._open: dict[int, list[Span]] = {}     # thread id -> open spans
        _listen()

    @property
    def enabled(self) -> bool:
        return True

    def now(self) -> float:
        return time.perf_counter() - self._origin

    def start(self, name: str, **attrs) -> Span:
        tid = threading.get_ident()
        stack = self._open.setdefault(tid, [])
        sp = Span(name=name, ts_s=self.now(), attrs=dict(attrs), tid=tid,
                  id=next(self._ids),
                  parent=stack[-1].id if stack else None, _tracer=self,
                  _jit0=tuple(_jit_counts[k] for k in JIT_KINDS))
        stack.append(sp)
        if self.annotate:
            try:
                import jax
                sp._annotation = jax.profiler.TraceAnnotation(name)
                sp._annotation.__enter__()
            except Exception:        # profiler backend unavailable: host-only
                sp._annotation = None
        return sp

    def finish(self, span: Span, **attrs) -> Span:
        if span._tracer is None:                 # null span / double finish
            return span
        if attrs:
            span.attrs.update(attrs)
        if span._annotation is not None:
            span._annotation.__exit__(None, None, None)
            span._annotation = None
        span.dur_s = self.now() - span.ts_s
        for kind, n0 in zip(JIT_KINDS, span._jit0):
            if _jit_counts[kind] != n0:
                span.attrs[kind] = _jit_counts[kind] - n0
        span._tracer = None
        # removed wherever it sits: a span may finish out of order (the
        # round left open when training ends mid-round)
        stack = self._open.get(span.tid, [])
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is span:
                del stack[i]
                break
        with self._lock:
            self.spans.append(span)
        return span

    def span(self, name: str, **attrs) -> Span:
        """Context-manager span: finished (and recorded) on exit."""
        return self.start(name, **attrs)

    def record(self, name: str, ts_s: float, dur_s: float, *,
               parent: int | None = None, **attrs) -> Span:
        """Append an already-measured interval (the per-stage attribution
        path: ``sync_stage_spans`` splits one measured sync over its
        collective stages), as a child of the span whose id is
        ``parent``."""
        sp = Span(name=name, ts_s=ts_s, dur_s=float(dur_s),
                  attrs=dict(attrs), tid=threading.get_ident(),
                  id=next(self._ids), parent=parent)
        with self._lock:
            self.spans.append(sp)
        return sp


class NullTracer(Tracer):
    """The disabled tracer ``fit`` uses when none is passed: every hook
    is a cheap no-op, nothing is recorded and no listener is registered,
    so the untraced code path stays byte-for-byte the pre-trace
    behavior."""

    def __init__(self):                  # no clock, no lock, no list
        self.fence = False
        self.annotate = False
        self.metrics = None
        self.spans = []

    @property
    def enabled(self) -> bool:
        return False

    def now(self) -> float:
        return 0.0

    def start(self, name: str, **attrs) -> Span:
        return _NULL_SPAN

    def finish(self, span: Span, **attrs) -> Span:
        return span

    def span(self, name: str, **attrs) -> Span:
        return _NULL_SPAN

    def record(self, name: str, ts_s: float, dur_s: float, *,
               parent: int | None = None, **attrs) -> Span:
        return _NULL_SPAN


NULL = NullTracer()


def self_time_s(spans) -> dict:
    """Span id -> self time in seconds: the span's duration minus the
    part of its interval that its children (spans naming it as
    ``parent``) cover."""
    kids = defaultdict(list)
    for s in spans:
        if s.parent is not None and s.dur_s is not None:
            kids[s.parent].append((s.ts_s, s.ts_s + s.dur_s))
    out = {}
    for s in spans:
        if s.dur_s is None:
            continue
        end = s.ts_s + s.dur_s
        covered, reach = 0.0, s.ts_s
        for a, b in sorted(kids.get(s.id, ())):
            a, b = max(a, reach), min(b, end)
            if b > a:
                covered += b - a
                reach = b
        out[s.id] = s.dur_s - covered
    return out


def clock_offset_ns(spans, profiled) -> int | None:
    """Offset from a tracer's clock to a profiler capture's, in ns.

    ``spans`` are a tracer's finished spans; ``profiled`` holds
    ``(start_ns, end_ns, name)`` of the same annotated spans as a
    ``jax.profiler`` capture holds them.  Spans of each name are paired
    in time order, using only names whose counts agree on both sides;
    the result is the median of (profiled start - span start), so that
    ``span.ts_s * 1e9 + offset`` is the span's start on the capture's
    clock.  None when no name pairs.
    """
    mine, theirs = defaultdict(list), defaultdict(list)
    for s in spans:
        if s.dur_s is not None:
            mine[s.name].append(s.ts_s * 1e9)
    for start, _, name in profiled:
        theirs[name].append(start)
    diffs = []
    for name, starts in mine.items():
        other = theirs.get(name, ())
        if len(other) == len(starts):
            diffs += [o - m for m, o in zip(sorted(starts), sorted(other))]
    if not diffs:
        return None
    return round(statistics.median(diffs))


def sync_stage_spans(tracer: Tracer, plan, scope: str, parent: Span,
                     *, seconds: float | None = None) -> list[tuple[int, float]]:
    """Emit one ``collective`` child span per collective stage of
    ``plan.schedule(scope)``, apportioning the measured sync duration
    over the stages by their ring-model wire-byte estimates — the exact
    mirror of how ``CommsLedger.record_plan`` scales stage byte
    estimates to a measured HLO total.  Each span carries the SAME
    ``stage`` id (index among the scope's collective stages) the ledger
    rows carry, so a stage can be joined bytes<->seconds across the two
    streams.  Spans are marked ``attributed=True``: the split is modeled
    (the sync executes as one fused program), only the total is
    measured.

    Returns ``[(stage_id, seconds), ...]``; empty on a disabled tracer
    or an unfinished parent.
    """
    total = parent.dur_s if seconds is None else seconds
    if not tracer.enabled or total is None:
        return []
    stages = list(plan.collective_stages(scope))
    if not stages:
        return []
    est = sum(s.wire_bytes for s in stages)
    shares = ([s.wire_bytes / est for s in stages] if est > 0
              else [1.0 / len(stages)] * len(stages))
    out = []
    t = parent.ts_s
    for i, (s, w) in enumerate(zip(stages, shares)):
        dur = total * w
        tracer.record("collective", t, dur, parent=parent.id,
                      stage=i, scope=scope,
                      buckets=list(s.buckets), compression=s.compression,
                      group=s.group, wire_bytes=s.wire_bytes,
                      collectives=s.collectives, coalesced=s.coalesced,
                      attributed=True)
        out.append((i, dur))
        t += dur
    return out
