"""Share of its roofline that ``kernels/minskew.py`` reaches, %.

The kernel's device events are the Pallas calls (``tpu_custom_call``)
inside the round loop's program (``jit_run_vec_tape``) that take the
int8 scope-membership matrix; a Pallas kernel elsewhere is not counted.  The work is one
eligibility round per dispatch round of the loop
(``workcount.minskew_round``); the least time is the larger of its
operations over the peak rate and its bytes over the HBM bandwidth."""
import workcount


def is_minskew(hlo: str) -> bool:
    return "tpu_custom_call" in hlo and " s8[" in hlo


def read(ctx):
    evs = ctx.trace.op_events(is_minskew, module="jit_run_vec_tape")
    busy = sum(e - s for s, e, _ in evs) * 1e-9
    rounds = ctx.counts.get("rounds", 0)
    if not evs or not rounds or busy <= 0:
        return None
    ops, bytes_ = workcount.minskew_round(ctx.counts["n_tasks"],
                                          ctx.counts["n_scopes"])
    return 100.0 * rounds * workcount.least_s(ops, bytes_, ctx.peaks) / busy
