"""Mean host duration of ``fit``'s ``readback`` span per local step, in
ms.

The span holds the read-back of the step's metrics to the host
(``float`` of each), after the step and any sync were dispatched: the
host waits there for the device to finish the step.
"""


def read(w):
    durs = [s.dur_s for s in w.spans if s.name == "readback"]
    if not durs:
        return None
    return 1000.0 * sum(durs) / len(durs)
