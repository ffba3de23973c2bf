"""The whole decode step's share of the chip's peak, %, for the latent
expert model: for each traced decode step, the least time its work needs
(``workcount_mla.decode_step``: the larger of its FLOPs over the bf16
peak and its bytes over the HBM bandwidth), summed, over the sum of the
steps' measured spans (the live ledger's host-clock spans, which become
simulated time)."""
import workcount
import workcount_mla


def read(ctx):
    steps = ctx.counts.get("decode_steps", [])
    if not steps:
        return None
    need = sum(workcount.least_s(*workcount_mla.decode_step(
        ctx.config, ctx.counts["batch"], context), ctx.peaks)
        for context, _ in steps)
    return 100.0 * need / (sum(ns for _, ns in steps) * 1e-9)
