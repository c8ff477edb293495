"""Device time of the sync program per round, in ms.

The sync is the program that ``fit`` launches inside its ``sync`` span
(``core/syncplan`` + ``local_sgd.sync``, collectives included): the sum
of its executions' device durations, averaged over the chips, over the
window's rounds.
"""


def read(w):
    seconds, count = w.trace.program_s("sync")
    if not count:
        return None
    return 1000.0 * seconds / w.rounds
