"""Host time a sweep spends moving state between its per-variant and
its batched form, per sweep call, ms: the program's ``sim.stage`` spans
(tapes and initial states stacked onto the device) and ``sim.unstack``
spans (the batched final state sliced back per variant, with its
fixpoint flags fetched), in ``sim/vectorized.py`` ``sweep_vectorized``."""

import progtrace


def read(ctx):
    p, n = progtrace.of(ctx), ctx.counts.get("calls", 0)
    if p is None or not n:
        return None
    ns = (p.span_ns("livestack.sim.stage")
          + p.span_ns("livestack.sim.unstack"))
    return ns / n * 1e-6
