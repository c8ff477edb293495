"""The driver-loop readers: batch, dispatch, read-back and set-up spans
of ``fit``, and the device idle time under batch draw and dispatch."""
from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

import smoke  # noqa: F401  (puts the repo root on sys.path)
from benchmarks.chip import registry, trace_reduce
from benchmarks.chip.trace_reduce import Chip, Reduced

DATA = Path(__file__).resolve().parent / "data"
META = json.loads((DATA / "tiny.json").read_text())
READERS = ("batch_ms", "step_dispatch_ms", "readback_ms", "fit_setup_ms",
           "driver_idle_ms")
US = 1000                       # ns
OFF = 1_000_000                 # the profiler's clock, ahead by 1 ms

# (name, start, end) on the tracer's clock, in microseconds: one fit of
# one round of two local steps, as fit nests its spans
HOST = [("fit_setup", 0, 50), ("round", 50, 150),
        ("local_steps", 50, 90), ("batch", 50, 60),
        ("step_dispatch", 60, 90), ("readback", 90, 110),
        ("local_steps", 110, 140), ("batch", 110, 115),
        ("step_dispatch", 115, 140), ("sync", 140, 150),
        ("readback", 150, 160)]


def made_up_window(chips):
    spans = []
    for i, (name, s, e) in enumerate(HOST, start=1):
        spans.append(types.SimpleNamespace(name=name, ts_s=s * 1e-6,
                                           dur_s=(e - s) * 1e-6, id=i,
                                           parent=None))
    # the capture holds the annotated spans its reduction keeps, on its
    # own clock; one start is off by 0.4 us, which the median ignores
    kept = [(s * US + OFF, e * US + OFF, n) for n, s, e in HOST
            if n in trace_reduce.LABEL_SPANS]
    kept[-1] = (kept[-1][0] + 400, kept[-1][1], kept[-1][2])
    kept.append((OFF, 160 * US + OFF, trace_reduce.WINDOW))
    red = Reduced(window=(OFF, 160 * US + OFF), chips=chips,
                  spans=sorted(kept))
    return trace_reduce.Window(trace=red, spans=spans, rounds=1,
                               local_steps=2, tokens=1, wall_s=160e-6,
                               chips=len(chips), peak={},
                               flops_per_token=1.0)


def ops(*intervals):
    return Chip(ops=[(s * US + OFF, e * US + OFF, "op")
                     for s, e in intervals])


def read(name, w):
    return registry.load_reader(name)(w)


def test_the_span_readers_average_their_spans():
    w = made_up_window([ops((0, 160))])
    assert read("batch_ms", w) == pytest.approx((10 + 5) / 2 / 1000)
    assert read("step_dispatch_ms", w) == pytest.approx((30 + 25) / 2 / 1000)
    assert read("readback_ms", w) == pytest.approx((20 + 10) / 2 / 1000)
    assert read("fit_setup_ms", w) == pytest.approx(50 / 1000)


def test_driver_idle_counts_idle_time_under_batch_and_dispatch():
    # busy 0-55 (straddles batch 50-60), 80-105 (straddles the end of
    # dispatch at 90), 125-150; idle under batch and dispatch: 55-80
    # (25 us) and 110-125 (15 of the gap 105-125, which starts under
    # readback): 40 us over 2 local steps
    idle_chip = ops((0, 55), (80, 105), (125, 150))
    busy_chip = ops((0, 160))
    w = made_up_window([busy_chip, idle_chip])
    assert read("driver_idle_ms", w) == pytest.approx(40 / 2 / 1000)
    # the idlest chip decides
    assert read("driver_idle_ms", made_up_window([busy_chip])) == 0.0


def test_driver_idle_clips_to_the_window():
    w = made_up_window([ops((80, 105), (125, 150))])
    w.trace.window = (55 * US + OFF, 160 * US + OFF)
    # 50-55 is outside the window: idle 55-80 and 110-125
    assert read("driver_idle_ms", w) == pytest.approx(40 / 2 / 1000)


def test_driver_idle_needs_spans_that_pair_with_the_capture():
    w = made_up_window([ops((0, 55))])
    w.trace.spans = [s for s in w.trace.spans
                     if s[2] == trace_reduce.WINDOW]
    assert read("driver_idle_ms", w) is None


@pytest.mark.parametrize("name", READERS)
def test_each_driver_loop_reader_reads_nothing_in_the_recorded_trace(name):
    """The recorded trace's program had none of these spans."""
    red = trace_reduce.reduce(str(DATA / "tiny.xplane.pb"), chips=1)
    spans = [types.SimpleNamespace(name=n, ts_s=s * 1e-9,
                                   dur_s=(e - s) * 1e-9, parent=None)
             for s, e, n in red.spans]
    w = trace_reduce.Window(trace=red, spans=spans, rounds=META["rounds"],
                            local_steps=META["local_steps"], tokens=1,
                            wall_s=META["wall_s"], chips=1, peak={},
                            flops_per_token=1.0)
    assert read(name, w) is None


@pytest.mark.parametrize("name", READERS)
def test_each_driver_loop_metric_is_declared_as_the_driver_loop(name):
    entry = next(m for m in registry.load_benchmark()["per_layer"]
                 if m["name"] == name)
    assert entry["layer"] == "driver loop" and entry["unit"] == "ms"
    assert entry["moves"] == "tokens_per_s" and entry["better"] == "lower"
