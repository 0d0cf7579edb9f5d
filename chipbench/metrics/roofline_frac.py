"""``roofline_frac``: the whole program's share of its roofline (percent).

The least time a call could take on the chips is the larger of its
essential bytes over their HBM bandwidth and its essential flops over
their peak, both computed by the cell's entry from its shapes alone (one
read of the input, one write of the output; 5·N·log2 N flops per complex
transform).  Over all calls in the window, that time is compared with the
device's busy time, averaged over the cell's devices."""

from chipbench.lib.intervals import length, spans, union


def reduce(tr: dict):
    if not any(tr["devices"]) or tr["calls"] == 0:
        return None
    lo, hi = tr["window_ns"]
    chips = tr["chips"]
    ess, pk = tr["essential"], tr["peaks"]
    least_s = max(
        ess["bytes"] / (chips * pk["hbm_bytes_per_s"]),
        ess["flops"] / (chips * pk["flops_per_s"]),
    )
    busy_s = 1e-9 * sum(length(union(spans(ev), lo, hi)) for ev in tr["devices"]) / chips
    return 100.0 * tr["calls"] * least_s / busy_s
