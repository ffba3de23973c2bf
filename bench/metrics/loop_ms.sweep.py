"""Device time of the batched round loop (``core/engine_jax.py``
``run_vec_tape`` under ``vmap``, program ``jit_run_vec_tape``) per sweep
call, ms."""


def read(ctx):
    n = ctx.counts.get("calls", 0)
    ns = ctx.trace.module_ns("jit_run_vec_tape")
    return ns / n * 1e-6 if n and ns else None
