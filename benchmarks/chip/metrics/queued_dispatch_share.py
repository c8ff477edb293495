"""Share of ``fit``'s ``step_dispatch`` spans in the window that found the
program dispatched before them still running, in %.

``fit`` marks each ``step_dispatch`` span ``queued`` 1 where an output of
the previous program (a local step or a sync) was not ready when the
dispatch began (``jax.Array.is_ready``, which does not block), else 0:
a queued step waits on the device behind a running program instead of
leaving the chip idle while it is dispatched. A program whose spans
carry no ``queued`` attribute gives nothing.
"""


def read(w):
    marks = [s.attrs["queued"] for s in w.spans
             if s.name == "step_dispatch" and "queued" in getattr(
                 s, "attrs", {})]
    if not marks:
        return None
    return 100.0 * sum(1 for q in marks if q) / len(marks)
