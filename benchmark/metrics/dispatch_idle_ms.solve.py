"""Device idle time inside the program's `solver.dispatch` spans per
solve, in ms: the union of those spans on the host plane of the trace,
less the device's busy intervals (the first device, as `idle_gaps`
reads it), over the solves. The span is the service's device call
(transfer, launch, kernel, wait), so this is the part of the call in
which the device waits on the host. Read from the reduced trace's
intervals rather than from `idle_gaps`, which puts each whole gap
between two kernels under the span open at its midpoint. A device-trace
metric reads a profiled window: the profiler slows the host side of the
call, so this idle time is longer than in an untraced run."""

from benchmark import trace as T

LABEL = "solver.dispatch"


def read(ctx):
    solves = ctx.window.layers.get("solves")
    if ctx.trace is None or not solves:
        return None
    r = ctx.trace
    spans = T._union(
        (max(start, r.lo), min(end, r.hi)) for name, start, end in r._host if name == LABEL
    )
    if not spans:
        return None
    idle = T._subtract(spans, r._busy[r.devices[0]])
    return T._length(idle) / 1e9 / solves * 1e3
