"""Host time spent lowering the scenario to tapes and quantizing them
(``sim/vectorized.py`` ``_lower``, ``_quantize``), per run call, ms:
the benchmark's ``bench.lower`` and ``bench.quantize`` spans."""


def read(ctx):
    n = ctx.counts.get("calls", 0)
    ns = ctx.trace.span_ns("bench.lower") + ctx.trace.span_ns("bench.quantize")
    return ns / n * 1e-6 if n and ns else None
