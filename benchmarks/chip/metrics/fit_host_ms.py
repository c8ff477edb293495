"""Mean host duration of ``fit``'s ``local_steps`` span, in ms.

The span is unfenced, so it times the driver loop's own work for a step:
drawing and stacking the batch and dispatching the local step.
"""


def read(w):
    durs = [s.dur_s for s in w.spans if s.name == "local_steps"]
    if not durs:
        return None
    return 1000.0 * sum(durs) / len(durs)
