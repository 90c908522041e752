"""Device-to-host reads answered from the read tape, per answered request.

Source: `QueryStats.replayed_reads` of the window's answered requests: the
program's count of 0-d count reads that a warm replay answered from its
plan's recorded read tape instead of the device. None where the program
does not count them.
"""


def read(w):
    counts = [getattr(s, "replayed_reads", None) for s in w.stats]
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    return sum(counts) / len(counts)
