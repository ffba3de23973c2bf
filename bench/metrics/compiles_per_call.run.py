"""Executables JAX built (backend compiles and loads from the
persistent compilation cache) per traced run call, counted by the
program's compile counter (``repro/obs.py``): its ``livestack.compile``
events in the traced window over the traffic's ``trace_calls``."""

import progtrace


def read(ctx):
    p = progtrace.of(ctx)
    if p is None:
        return None
    return (p.count(progtrace.COMPILE)
            / int(ctx.traffic.get("trace_calls", 1)))
