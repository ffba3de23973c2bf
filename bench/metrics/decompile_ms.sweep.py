"""Host time spent decompiling the round loop's arrays into reports
(``sim/vectorized.py`` ``_decompile``, with the batched hub fan-out),
per sweep call, ms: the benchmark's ``bench.decompile`` spans."""


def read(ctx):
    n = ctx.counts.get("calls", 0)
    ns = ctx.trace.span_ns("bench.decompile")
    return ns / n * 1e-6 if n and ns else None
