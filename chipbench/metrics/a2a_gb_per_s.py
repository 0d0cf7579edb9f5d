"""``a2a_gb_per_s``: the bytes each device sends to the others per second
of collective device time (GB/s), mean over the cell's devices.

Bytes are the entry's ``essential["a2a_bytes"]`` (what one call sends
off-chip per device, counted from the shapes alone) times the calls in the
window.  Time is the union of the device's collective intervals: a
collective that is one device event (as the compiled pencil program's
synchronous ``all-to-all`` instructions are on a v5e) counts for that
event; an asynchronous pair counts from the start of its ``-start`` event
to the end of its ``-done``, the pairs matched in the order they run.
``None`` where the entry counts no such bytes or no collective ran."""

from chipbench.lib.intervals import length, union


def intervals(ev, coll, labels) -> list:
    """``(start, end)`` of each collective in one device's events."""
    out, started = [], []
    for name, s, d in sorted(ev, key=lambda e: e[1]):
        if name not in coll:
            continue
        label = labels.get(name, "")
        if label.endswith("-start"):
            started.append(s)
        elif label.endswith("-done") and started:
            out.append((started.pop(0), s + d))
        else:
            out.append((s, s + d))
    return out


def reduce(tr: dict):
    sent = tr["essential"].get("a2a_bytes", 0) * tr["calls"]
    coll = set(tr["ops"]["collective"])
    lo, hi = tr["window_ns"]
    rates = []
    for ev in tr["devices"]:
        busy_ns = length(union(intervals(ev, coll, tr.get("labels", {})), lo, hi))
        if busy_ns > 0:
            rates.append(sent / (busy_ns * 1e-9) / 1e9)
    if not sent or not rates:
        return None
    return sum(rates) / len(rates)
