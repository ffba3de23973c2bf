"""LiveStack scheduler hot spot as a Pallas TPU kernel.

Per dispatch round the scheduler computes (paper §3.2):
  1. scope minima: min vtime over runnable members of each scope,
  2. eligibility:  vtask runnable AND vtime <= min + skew in EVERY scope.

At cluster scale (10^4..10^5 vtasks x 10^2..10^3 scopes) this is the
per-round bottleneck — a masked segmented-min plus a masked all-reduce
over the scope axis.  Two passes tile the (N x S) membership matrix into
VMEM blocks.  The minima pass runs grid (s_blocks, n_blocks) with the N
reduction innermost, so each (1, bs) output block is initialised,
accumulated and written back before the next one is visited.  The
eligibility pass runs grid (n_blocks, s_blocks) with the S reduction
innermost, accumulating into its resident (bn, 1) output block.

Layout notes: vtimes are int32 ticks (see engine_jax); membership is a
dense int8 mask in HBM, widened to int32 per block in VMEM before it is
compared.  Every operand is 2-D: per-vtask vectors are (N, 1)
columns and per-scope vectors (1, S) rows, so the only broadcasts in
the kernels are lane and sublane broadcasts of int32 values.  Blocks
are (512, 128) at fleet shapes and the full extent when a dimension is
smaller than one block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INF = 2**30  # python int: jnp scalars would be captured as consts
_NEVER = 2**31 - 1   # threshold of a scope that gates nobody


def _member(member_ref):
    # widen before comparing: an int8 compare yields a packed mask that
    # Mosaic cannot relayout against the broadcast int32 operand
    return member_ref[...].astype(jnp.int32) != 0


def _minima_kernel(vr_ref, member_ref, min_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        min_ref[...] = jnp.full_like(min_ref, INF)

    vm = jnp.where(_member(member_ref), vr_ref[...], INF)   # (bn, bs)
    min_ref[...] = jnp.minimum(min_ref[...],
                               jnp.min(vm, axis=0, keepdims=True))


def _elig_kernel(vtime_ref, member_ref, thr_ref, ok_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        ok_ref[...] = jnp.ones_like(ok_ref)

    within = (vtime_ref[...] <= thr_ref[...]).astype(jnp.int32)
    ok = jnp.where(_member(member_ref), within, 1)           # (bn, bs)
    ok_ref[...] = jnp.minimum(ok_ref[...],
                              jnp.min(ok, axis=1, keepdims=True))


def _block(dim: int, block: int) -> int:
    """One block spanning the whole dimension when it fits, else
    ``block`` (the dimension is then padded to a multiple of it)."""
    return dim if dim <= block else block


def minskew(vtime, runnable, membership, skew, *, block_n=512,
            block_s=128, interpret=False):
    """Returns (scope minima (S,), eligibility (N,) int8).

    vtime (N,) int32; runnable (N,) int8; membership (N, S) int8;
    skew (S,) int32.  Natively, ``block_n`` must be a multiple of 32
    (int8 sublane tiling) and ``block_s`` of 128."""
    n, s = membership.shape
    bn, bs = _block(n, block_n), _block(s, block_s)
    n_pad = pl.cdiv(n, bn) * bn
    s_pad = pl.cdiv(s, bs) * bs
    nb, sb = n_pad // bn, s_pad // bs
    live = runnable != 0
    # padded rows and columns are non-members, so they never matter
    membership = jnp.pad(membership, ((0, n_pad - n), (0, s_pad - s)))
    vr = jnp.pad(jnp.where(live, vtime, INF), (0, n_pad - n),
                 constant_values=INF).reshape(n_pad, 1)
    col = pl.BlockSpec((bn, 1), lambda j, i: (i, 0))

    minima = pl.pallas_call(
        _minima_kernel,
        grid=(sb, nb),
        in_specs=[col, pl.BlockSpec((bn, bs), lambda j, i: (i, j))],
        out_specs=pl.BlockSpec((1, bs), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, s_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="minskew_minima",
    )(vr, membership)

    skew = jnp.pad(skew, (0, s_pad - s)).reshape(1, s_pad)
    thr = jnp.where(minima == INF, _NEVER, minima + skew)
    v = jnp.pad(vtime, (0, n_pad - n)).reshape(n_pad, 1)
    ok = pl.pallas_call(
        _elig_kernel,
        grid=(nb, sb),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, bs), lambda i, j: (i, j)),
            pl.BlockSpec((1, bs), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="minskew_eligible",
    )(v, membership, thr)

    elig = (ok[:n, 0] != 0) & live
    return minima[0, :s], elig.astype(jnp.int8)
