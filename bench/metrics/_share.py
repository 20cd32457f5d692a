"""Shared arithmetic of the roofline readers (not a metric)."""


def matched(tr, role, costs) -> int:
    """How many of the dispatched ``costs`` the trace's ``role`` events
    cover: the trace starts with the device idle, so its k-th event of a
    role is the k-th dispatch of that role in the sub-window."""
    n = len(tr.of_role(role))
    if n > len(costs):
        raise ValueError(f"trace holds {n} {role} events but the harness "
                         f"dispatched {len(costs)}")
    return n


def roofline(rec, role, costs):
    tr = rec.trace
    if tr is None:
        return None
    evs = tr.of_role(role)
    if not evs:
        return None
    n = matched(tr, role, costs)
    least = sum(rec.least_time(f, b) for f, b in costs[:n])
    dev = sum(e - s for _, s, e in evs)
    return 100.0 * least / dev if dev > 0 and least > 0 else None
