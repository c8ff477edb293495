"""Telemetry: on-device round statistics + host-side comms ledger.

The measurement half of the adaptive sync controller (ISSUE 3):

* :mod:`repro.telemetry.stats` — a :class:`StatsAccumulator` carried in
  ``LocalSGDState`` that fuses per-round statistics out of the resident
  dtype buckets (grad-norm^2 / update-norm^2 ride the already-launched
  fused optimizer kernels; inter-worker gradient diversity comes from a
  pre-/post-mean norm pair at sync; per-bucket compression error from
  the compressor residual).
* :mod:`repro.telemetry.ledger` — a host-side :class:`CommsLedger`
  counting bytes / collectives per sync round, either measured from
  compiled HLO via ``roofline/hlo.parse_collectives`` or from the
  analytic ring-cost model over the flatbuf bucket layout.
* :mod:`repro.telemetry.trace` — the seconds-denominated sensor layer
  (ISSUE 8): a span-based :class:`Tracer` around rounds, sync stages,
  and controller decisions, with opt-in ``block_until_ready`` fencing
  and ``jax.profiler.TraceAnnotation`` pass-through; parent links,
  per-span JAX trace/compile counts, and ``clock_offset_ns`` to lay the
  spans on a profiler capture's clock.
* :mod:`repro.telemetry.metrics` — counters/gauges/histograms with
  Prometheus text exposition, fed from the RoundReport/ledger stream.
* :mod:`repro.telemetry.export` — Perfetto trace JSON, Prometheus
  files, the run manifest, and the CI schema validators.
"""
from repro.telemetry.ledger import CommsLedger, analytic_sync_cost, hlo_sync_cost
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.stats import (StatsAccumulator, accumulate_step,
                                   init_stats, record_sync, round_summary)
from repro.telemetry.trace import (NULL, Span, Tracer, clock_offset_ns,
                                   jit_events, self_time_s, sync_stage_spans)

__all__ = [
    "StatsAccumulator", "init_stats", "accumulate_step", "record_sync",
    "round_summary", "CommsLedger", "analytic_sync_cost", "hlo_sync_cost",
    "Tracer", "Span", "NULL", "sync_stage_spans", "clock_offset_ns",
    "jit_events", "self_time_s", "MetricsRegistry",
]
