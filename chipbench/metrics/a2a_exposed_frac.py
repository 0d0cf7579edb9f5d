"""``a2a_exposed_frac``: the share of the device's busy time in which a
collective runs and no other device operation does, mean over the cell's
devices (percent).  This is the communication that compute does not hide,
which the pencil schedule's chunked transposes exist to hide.

The collectives are the compiled program's collective instructions
(``tr["ops"]["collective"]``); each of their device events counts for its
own interval, so an asynchronous ``-start`` / ``-done`` pair counts the
time the device spends in those two events, not the transfer between
them that other operations overlap.  ``None`` where the program has no
collective or none ran in the window."""

from chipbench.lib.intervals import length, minus, union


def reduce(tr: dict):
    coll = set(tr["ops"]["collective"])
    lo, hi = tr["window_ns"]
    shares = []
    for ev in tr["devices"]:
        pieces = lambda keep: union([(s, s + d) for n, s, d in ev if keep(n)], lo, hi)  # noqa: E731
        mine = pieces(lambda n: n in coll)
        if not mine:
            continue
        exposed = minus(mine, pieces(lambda n: n not in coll))
        shares.append(length(exposed) / length(pieces(lambda n: True)))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
