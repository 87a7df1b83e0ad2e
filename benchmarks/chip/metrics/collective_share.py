"""Mesh collectives: device time of the collective ops (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, each whole or
as its start / done halves) over the device's busy time in the traced
window, both averaged over the chips (%). A trace with no collective reads
nothing."""


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or t.collective_s <= 0:
        return None
    return 100.0 * t.collective_s / t.busy_s
