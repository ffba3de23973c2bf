"""Share of its roofline that ``kernels/hub_route.py`` reaches, %.

The kernel's device events are the Pallas calls (``tpu_custom_call``)
that run inside the benchmark's ``bench.hub_route`` host spans, which
enclose each call of the kernel's entry until its result is ready: the
fan-out passes over the sorted messages that decompiling runs
(``workcount.hub_route_pass`` per event).  A Pallas kernel that runs
anywhere else is not counted."""
import workcount


def is_pallas(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


def read(ctx):
    evs = ctx.trace.op_events(is_pallas, span="bench.hub_route")
    busy = sum(e - s for s, e, _ in evs) * 1e-9
    m = ctx.counts.get("n_msgs", 0)
    if not evs or not m or busy <= 0:
        return None
    ops, bytes_ = workcount.hub_route_pass(m)
    return 100.0 * len(evs) * workcount.least_s(ops, bytes_, ctx.peaks) / busy
