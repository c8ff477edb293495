"""Mean host duration of ``fit``'s ``fit_setup`` span per ``fit`` call,
in ms.

The span covers ``fit`` before its loop: the weights made eagerly from
the seed, the state initialised, the sync plan and controller set up.
"""


def read(w):
    durs = [s.dur_s for s in w.spans if s.name == "fit_setup"]
    if not durs:
        return None
    return 1000.0 * sum(durs) / len(durs)
