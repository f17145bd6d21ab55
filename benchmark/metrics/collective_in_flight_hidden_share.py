"""Of the time a step's collectives are in flight, the share during which the
core ran something else: for every collective of the program's
`kind="collectives"` record, from the start of its first instruction to the
end of its last (its `start` and its `done`, paired by the record's number of
the collective; a `sync` or `fused` one is in flight for its own event and
nothing can hide it), the time covered by `XLA Ops` events that are NOT the
record's own-role instructions, summed over the collectives and the devices,
over the time in flight.  100 is every byte moved behind computation, 0 a step
whose collectives are all synchronous (the dp4 cell under GSPMD).  With
`collective_own_time_share` it tells an arm that HIDES its all-reduce from one
that drops it: both read ~0 there, and only the first reads high here.

A `while` or `conditional` event encloses its body's: the time that counts as
"something else" is the union of the other events less the union of the
record's own.  One pass over the window's events a device, no own-time table.
Nothing where the program wrote no record, the run has no device trace, or no
collective of the record ran in the window."""
from bisect import bisect_right

from benchmark import program_trace
from benchmark import trace_reduce as tr
from benchmark.metrics import collective_bytes_per_step
from benchmark.metrics.collective_own_time_share import OWN_ROLES

LAYER = 'multi-chip (parallel/*)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def in_flight(events, instructions: dict, window) -> tuple:
    """([(start, end) of every collective's flight], the merged intervals in which something else ran) of one
    `XLA Ops` line inside `window`."""
    lo, hi = window
    flights, open_, own, other = [], {}, [], []
    for name, s, d, *_ in sorted(events, key=lambda e: e[1]):
        if s < lo or s + d > hi:
            continue
        known = instructions.get(tr.instruction_of(name))
        if known is None or known[1] not in OWN_ROLES:
            other.append((s, s + d))
            continue
        own.append((s, s + d))
        role, number = known[1], known[3]
        if role == "start":
            open_[number] = s
        elif role == "done":
            if number in open_:
                flights.append((open_.pop(number), s + d))
        else:
            flights.append((s, s + d))
    return flights, tr.subtract(tr.union(other), tr.union(own))


def covered(intervals, merged) -> float:
    """The time of `intervals` (which may overlap one another) that the merged, sorted `merged` covers."""
    starts = [s for s, _ in merged]
    upto = [0.0]
    for s, e in merged:
        upto.append(upto[-1] + e - s)

    def before(t):     # the covered time left of t
        i = bisect_right(starts, t)
        return upto[i] - max(0.0, merged[i - 1][1] - t) if i else 0.0

    return sum(before(e) - before(s) for s, e in intervals)


def read(ctx: dict):
    record = collective_bytes_per_step.step_record(ctx)
    planes = program_trace.traced_planes(ctx) if record and ctx.get("executables") else None
    window = program_trace.traced_window(planes) if planes else None
    if window is None:
        return None
    flown = hidden = 0.0
    for _, by_line in program_trace.device_ops(planes):
        flights, elsewhere = in_flight(by_line["XLA Ops"], record["instructions"], window)
        flown += tr.total(flights)
        hidden += covered(flights, elsewhere)
    return 100.0 * hidden / flown if flown else None
