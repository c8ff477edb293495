"""Share of the traced window in which no operation ran, in %, on the
idlest chip: 1 - (union of device op intervals / window)."""


def read(w):
    if not w.trace.idle_share:
        return None
    return 100.0 * max(w.trace.idle_share)
