"""Device idle time under the driver loop's batch draw and step
dispatch, per local step, in ms.

The program's ``batch`` and ``step_dispatch`` spans are laid on the
profiler's clock by ``repro.telemetry.trace.clock_offset_ns``, which
pairs the tracer's spans with the same annotated spans in the capture.
On the idlest chip: the time inside the window and inside those spans
in which no operation ran, over the window's local steps. A faster
dispatch and a dispatch that overlaps the step both lower it.
"""

from benchmarks.chip.trace_reduce import _union

NAMES = ("batch", "step_dispatch")


def _covered(spans, busy):
    """ns of the sorted, disjoint ``spans`` that ``busy`` (sorted,
    disjoint) covers."""
    total, j = 0, 0
    for a, b in spans:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            total += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
    return total


def read(w):
    host = [s for s in w.spans if s.name in NAMES and s.dur_s is not None]
    if not host or not w.trace.chips:
        return None
    # imported here: a program without these spans lacks it too
    from repro.telemetry.trace import clock_offset_ns
    off = clock_offset_ns(w.spans, w.trace.spans)
    if off is None:
        return None
    w0, w1 = w.trace.window
    clipped = ((max(w0, s.ts_s * 1e9 + off),
                min(w1, (s.ts_s + s.dur_s) * 1e9 + off)) for s in host)
    spans = _union((a, b) for a, b in clipped if b > a)
    inside = sum(b - a for a, b in spans)
    idle = max(inside - _covered(spans, w.trace._busy(c))
               for c in w.trace.chips)
    return idle * 1e-6 / (w.rounds * w.local_steps)
