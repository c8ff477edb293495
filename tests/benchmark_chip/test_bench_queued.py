"""The ``queued_dispatch_share`` reader: the share of ``step_dispatch``
spans that ``fit`` marked ``queued``, and nothing from a program that
marks none."""
from __future__ import annotations

import types

import pytest

import smoke  # noqa: F401  (puts the repo root on sys.path)
from benchmarks.chip import registry

NAME = "queued_dispatch_share"


def span(name, **attrs):
    return types.SimpleNamespace(name=name, dur_s=1e-3, attrs=attrs)


def read(spans):
    return registry.load_reader(NAME)(types.SimpleNamespace(spans=spans))


@pytest.mark.parametrize("marks,share", [
    ((1, 1, 1, 1), 100.0),
    ((0, 1, 1, 1), 75.0),
    ((0, 0), 0.0),
    ((0, 1, 0, 1, 1), 60.0),
])
def test_the_share_counts_queued_step_dispatches(marks, share):
    spans = [span("step_dispatch", queued=q) for q in marks]
    # a sync's own mark and other spans do not count
    spans += [span("sync", queued=0), span("readback"), span("batch")]
    assert read(spans) == pytest.approx(share)


@pytest.mark.parametrize("spans", [
    [],
    [span("step_dispatch"), span("step_dispatch"), span("sync")],
    [types.SimpleNamespace(name="step_dispatch", dur_s=1e-3)],
    [span("sync", queued=1), span("local_steps")],
], ids=["no spans", "no attribute", "spans without attrs", "sync only"])
def test_a_program_that_marks_nothing_gives_none_not_zero(spans):
    """The parent's spans carry no ``queued``: the metric is left out of
    its line rather than read as 0%."""
    assert read(spans) is None


def test_the_metric_is_declared_as_the_driver_loop():
    entry = next(m for m in registry.load_benchmark()["per_layer"]
                 if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_span", "layer": "driver loop",
                     "moves": "tokens_per_s"}
