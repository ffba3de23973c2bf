"""Time of the hub fan-out passes that decompiling runs
(``sim/vectorized.py`` ``_batched_visibility``: the ``hub_route``
kernel's entry, or ``hub_visibility``, through the copy of its result to
the host), per run call, ms: the program's ``sim.fanout`` spans."""

import progtrace


def read(ctx):
    p, n = progtrace.of(ctx), ctx.counts.get("calls", 0)
    if p is None or not n:
        return None
    return p.span_ns("livestack.sim.fanout") / n * 1e-6
