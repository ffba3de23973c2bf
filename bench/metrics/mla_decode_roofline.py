"""Share of its roofline that the latent decode attention reaches, %.

The kernel's device events are the ops named ``mla_decode_attention``
(the Pallas kernel of ``kernels/mla_decode.py``) inside the benchmark's
``bench.decode`` host spans, which enclose each decode step until its
tokens are on the host.  Its work per traced step is
``workcount_mla.mla_attention``: the attended latent rows of every layer
read once, the absorbed queries read and the latent outputs written,
and the two products; the least time is the larger of the operations
over the peak rate and the bytes over the HBM bandwidth."""
import workcount
import workcount_mla


def is_mla_decode(hlo: str) -> bool:
    return hlo.lstrip("%").startswith("mla_decode_attention")


def read(ctx):
    evs = ctx.trace.op_events(is_mla_decode, span="bench.decode")
    busy = sum(e - s for s, e, _ in evs) * 1e-9
    steps = ctx.counts.get("decode_steps", [])
    if not evs or not steps or busy <= 0:
        return None
    need = sum(workcount.least_s(*workcount_mla.mla_attention(
        ctx.config, ctx.counts["batch"], context), ctx.peaks)
        for context, _ in steps)
    return 100.0 * need / busy
