"""Share of their roofline that the held and shared experts reach, %.

Their device events, inside the benchmark's ``bench.decode`` host spans:
the held experts' grouped matmuls (the compiler's ``ragged-dot`` ops,
which ``jax.lax.ragged_dot`` becomes on the TPU), the shared experts'
Pallas kernel (``moe_shared``, ``kernels/moe.py``), and the ops that
move a layer's expert weights out of the stacked weights (the ops whose
output has the shape of one layer's held or shared expert weights): the
compiler stages each layer's expert weights into on-chip memory in such
an op, so the weights are read from HBM there and the matmuls read them
on chip.  Their work per traced step is ``workcount_mla.experts``: every
held and shared expert's weights read once (a held expert goes unrouted
in a step with probability (58/64)^64, about 0.2 %, at this cell's
batch), and the products of the tokens routed to them."""
import workcount
import workcount_mla


def weight_shapes(cfg) -> set:
    """HLO result types of one layer's held and shared expert weights."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    eh, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * fe
    return {f"bf16[{eh},{d},{fe}]", f"bf16[{eh},{fe},{d}]",
            f"bf16[{d},{fs}]", f"bf16[{fs},{d}]"}


def is_expert(hlo: str, shapes: set) -> bool:
    name, _, rest = hlo.lstrip("%").partition(" = ")
    return (name.startswith(("ragged-dot", "moe_shared"))
            or rest.split("{", 1)[0] in shapes)


def read(ctx):
    shapes = weight_shapes(ctx.config)
    evs = ctx.trace.op_events(lambda h: is_expert(h, shapes),
                              span="bench.decode")
    busy = sum(e - s for s, e, _ in evs) * 1e-9
    steps = ctx.counts.get("decode_steps", [])
    if not evs or not steps or busy <= 0:
        return None
    need = len(steps) * workcount.least_s(
        *workcount_mla.experts(ctx.config, ctx.counts["batch"]), ctx.peaks)
    return 100.0 * need / busy
