"""``glue_frac``: the share of the device's busy time spent in operations
that are neither Pallas kernels nor collectives (XLA copies, gathers,
elementwise fusions between the passes), over all the cell's devices
(percent)."""

from chipbench.lib.intervals import length, minus, union


def reduce(tr: dict):
    lo, hi = tr["window_ns"]
    named = set(tr["ops"]["kernel"]) | set(tr["ops"]["collective"])
    busy = glue = 0
    for ev in tr["devices"]:
        every = union([(s, s + d) for _, s, d in ev], lo, hi)
        covered = union([(s, s + d) for n, s, d in ev if n in named], lo, hi)
        busy += length(every)
        glue += length(minus(every, covered))
    if busy == 0:
        return None
    return 100.0 * glue / busy
