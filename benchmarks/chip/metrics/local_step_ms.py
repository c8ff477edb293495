"""Device time of the local-step program per local step, in ms.

The local step is the program that ``fit`` launches inside its
``local_steps`` span (``launch/steps.build_train``'s jitted
``core/local_sgd`` step): the sum of its executions' device durations,
averaged over the chips, over the window's local steps.
"""


def read(w):
    seconds, count = w.trace.program_s("local_steps")
    if not count:
        return None
    return 1000.0 * seconds / (w.rounds * w.local_steps)
