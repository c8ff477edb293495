"""Trace reduction: a small trace recorded on the chip, and exact cases."""
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
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(str(DATA / "tiny.xplane.pb"), chips=1)


def window(red, **over):
    spans = [types.SimpleNamespace(name=n, dur_s=(e - s) * 1e-9)
             for s, e, n in red.spans]
    kw = dict(trace=red, spans=spans, rounds=META["rounds"],
              local_steps=META["local_steps"], tokens=4 * 2 * 64 * 6,
              wall_s=META["wall_s"], chips=1, peak=PEAK,
              flops_per_token=1e8)
    kw.update(over)
    return trace_reduce.Window(**kw)


def test_the_recorded_trace_reduces_to_what_it_read_when_recorded(red):
    assert red.window_s == pytest.approx(META["window_s"])
    assert red.busy_s == pytest.approx(META["busy_s"])
    assert list(red.program_s("local_steps")) == pytest.approx(
        META["local_steps_program"])
    assert list(red.program_s("sync")) == pytest.approx(
        META["sync_program"])


def test_every_launch_is_attributed_to_its_span(red):
    steps = META["rounds"] * META["local_steps"]
    s_step, n_step = red.program_s("local_steps")
    s_sync, n_sync = red.program_s("sync")
    assert n_step == steps and n_sync == META["rounds"]
    assert 0 < s_step and 0 < s_sync
    assert s_step + s_sync <= red.busy_s <= red.window_s
    assert red.window_s == pytest.approx(META["wall_s"], rel=0.05)


def test_the_breakdown_lists_ops_and_labelled_gaps(red):
    b = red.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    for name, sec in b["device_ops"]:
        assert sec > 0 and "=" not in name and not name.startswith("%")
        assert not trace_reduce.CONTAINER.match(name)
    labels = {"round", "local_steps", "sync", "fit, between spans"}
    assert {g[0] for g in b["idle_gaps"]} <= labels
    secs = [g[1] for g in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)


def test_the_per_layer_readers_read_the_trace(red):
    got = {m["name"]: registry.load_reader(m["name"])(window(red))
           for m in registry.load_benchmark()["per_layer"]}
    steps = META["rounds"] * META["local_steps"]
    assert got["local_step_ms"] == pytest.approx(
        1000 * red.program_s("local_steps")[0] / steps)
    assert got["sync_ms"] == pytest.approx(
        1000 * red.program_s("sync")[0] / META["rounds"])
    assert 0 < got["device_idle_share"] < 100
    assert 0 < got["mfu"] <= 100
    assert got["fit_host_ms"] > 0


def test_a_reader_with_nothing_to_read_returns_none():
    empty = Reduced(window=(0, 10), chips=[Chip()], spans=[])
    w = types.SimpleNamespace(trace=empty, spans=[], rounds=1,
                              local_steps=1)
    for name in ("local_step_ms", "sync_ms", "fit_host_ms"):
        assert registry.load_reader(name)(w) is None


def test_busy_idle_and_attribution_on_a_made_up_trace():
    # window 0..100; ops 10..30 and 20..40 overlap, 60..70 alone
    chip = Chip(ops=[(10, 30, "a"), (20, 40, "b"), (60, 70, "c"),
                     (5, 95, "while.1")],
                modules=[(12, 40, "m", 1), (60, 70, "m", 2)])
    spans = [(0, 100, trace_reduce.WINDOW), (5, 45, "round"),
             (0, 11, "local_steps"), (50, 55, "sync")]
    r = Reduced(window=(0, 100), chips=[chip], spans=spans)
    assert r.busy_s == pytest.approx(90e-9)
    assert r.idle_share == [pytest.approx(0.1)]
    assert r.program_s("local_steps") == (pytest.approx(28e-9), 1)
    assert r.program_s("sync") == (pytest.approx(10e-9), 1)
    assert [n for n, _ in r.top_ops()] == ["a", "b", "c"]
    r2 = Reduced(window=(0, 100),
                 chips=[Chip(ops=[(10, 30, "a"), (60, 70, "c")])],
                 spans=spans)
    assert r2.idle_gaps() == [["fit, between spans", pytest.approx(30e-9)],
                              ["round", pytest.approx(30e-9)],
                              ["local_steps", pytest.approx(10e-9)]]


def test_programs_pair_with_spans_by_launch_order_when_the_device_lags():
    """The device still runs the previous sync when the host has launched
    the next step: launch order, not start time, says which is which."""
    spans = [(0, 100, trace_reduce.WINDOW), (0, 5, "local_steps"),
             (6, 8, "sync"), (9, 12, "local_steps"), (13, 15, "sync")]
    chip = Chip(modules=[(10, 30, "step", 1), (30, 35, "sync", 2),
                         (35, 55, "step", 3), (55, 60, "sync", 4)])
    r = Reduced(window=(0, 100), chips=[chip], spans=spans)
    assert r.program_s("local_steps") == (pytest.approx(40e-9), 2)
    assert r.program_s("sync") == (pytest.approx(10e-9), 2)
