"""RG-LRU chunked linear-recurrence Pallas TPU kernel (recurrentgemma).

h_t = a_t * h_{t-1} + b_t with diagonal, input-dependent a_t.  The TPU
adaptation replaces the GPU "one-thread-per-channel sequential loop"
with a *chunked two-level scan* shaped for the VPU: the sequence axis is
tiled into (block_t x block_w) VMEM blocks; within a block the recurrence
is evaluated by the classic log-depth Blelloch-style doubling on VREGs
(log2(block_t) vector ops instead of block_t serial steps), and the
carry h propagates across sequence tiles through VMEM scratch (grid dim
``arbitrary``).  Width is embarrassingly parallel (lane dimension).

Inputs are fp32: log_a (B, S, W), b (B, S, W); optional initial state
h0 (B, W).  Output: h (B, S, W).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(log_a_ref, b_ref, h0_ref, out_ref, carry_ref, *,
            block_t, n_t):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = h0_ref[0]

    la = log_a_ref[0]                       # (bt, bw) fp32
    bv = b_ref[0]

    # log-depth inclusive scan of the affine recurrence within the block:
    # pairs (A, B) compose as (A2*A1, A2*B1 + B2); shift-and-combine
    # doubling over the time axis.
    A = jnp.exp(la)
    B = bv
    steps = int(math.log2(block_t))
    for s in range(steps):
        d = 1 << s
        A_shift = jnp.concatenate(
            [jnp.ones((d, A.shape[1]), A.dtype), A[:-d]], axis=0)
        B_shift = jnp.concatenate(
            [jnp.zeros((d, B.shape[1]), B.dtype), B[:-d]], axis=0)
        B = A * B_shift + B
        A = A * A_shift

    h_in = carry_ref[...]                   # (bw,)
    h = A * h_in[None, :] + B
    out_ref[0] = h
    carry_ref[...] = h[-1]


def rglru_scan(log_a, b, h0=None, *, block_t=256, interpret=False):
    """(B, S, W) fp32 -> (B, S, W).  S padded to a power-of-two block."""
    bsz, s, w = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, w), jnp.float32)
    block_t = min(block_t, 1 << int(math.ceil(math.log2(max(s, 1)))))
    assert block_t & (block_t - 1) == 0, "block_t must be a power of two"
    s_pad = pl.cdiv(s, block_t) * block_t
    if s_pad != s:
        # pad with a=1, b=0 (identity elements continue the carry)
        log_a = jnp.pad(log_a, ((0, 0), (0, s_pad - s), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, s_pad - s), (0, 0)))
    n_t = s_pad // block_t

    out = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, n_t=n_t),
        grid=(bsz, n_t),
        in_specs=[
            pl.BlockSpec((1, block_t, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_t, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, w), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, w), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, s_pad, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b, h0)
    return out[:, :s, :]
