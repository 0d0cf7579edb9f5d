"""``idle_frac``: the share of the traced window in which no operation runs
on the device, averaged over the cell's devices (percent)."""

from chipbench.lib.intervals import length, spans, union


def reduce(tr: dict):
    if not any(tr["devices"]):
        return None
    lo, hi = tr["window_ns"]
    idle = [1 - length(union(spans(ev), lo, hi)) / (hi - lo) for ev in tr["devices"]]
    return 100.0 * sum(idle) / len(idle)
