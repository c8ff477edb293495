"""Mean host duration of ``fit``'s ``batch`` span per local step, in ms.

The span is a child of ``local_steps``: drawing the next batch of every
worker from the data feed (and stacking it, under a batch scale).
"""


def read(w):
    durs = [s.dur_s for s in w.spans if s.name == "batch"]
    if not durs:
        return None
    return 1000.0 * sum(durs) / len(durs)
