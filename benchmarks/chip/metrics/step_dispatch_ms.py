"""Mean host duration of ``fit``'s ``step_dispatch`` span per local
step, in ms.

The span is a child of ``local_steps`` around the ``bundle.local_step``
call: the jitted step's argument handling over every leaf of the state
and its launch. It is unfenced, so the device's own step time is not in
it.
"""


def read(w):
    durs = [s.dur_s for s in w.spans if s.name == "step_dispatch"]
    if not durs:
        return None
    return 1000.0 * sum(durs) / len(durs)
