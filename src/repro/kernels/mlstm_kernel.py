"""Chunkwise-parallel mLSTM matrix-memory Pallas TPU kernel (xlstm).

The mLSTM cell C_t = f_t C_{t-1} + i_t k_t v_t^T has a (hd x hd) matrix
state per head — on GPU this is a warp-per-head serial loop; the TPU
adaptation keeps the *chunkwise* formulation (intra-chunk attention-like
MXU matmuls + an inter-chunk C/n carry) with the carry resident in VMEM
scratch across the chunk grid dimension:

  intra:  S_ij = (q_i . k_j) exp(A_i - A_j) i_j   (j <= i, within chunk)
  inter:  out_i += exp(A_i) (q_i C),  den_i += exp(A_i) (q_i . n)
  carry:  C' = exp(A_L) C + sum_j exp(A_L - A_j) i_j k_j v_j^T

All matmuls are MXU-shaped ((L x hd) @ (hd x hd), (L x L) @ (L x hd));
gates/decays are fp32 VPU ops.  Matches ``repro.models.xlstm
.mlstm_chunkwise`` (same gate convention: i = exp(min(i_raw, 8)),
f = sigmoid) and is oracle-tested against the sequential step form.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I_CAP = 8.0


def _kernel(q_ref, k_ref, v_ref, ig_ref, fg_ref, out_ref,
            c_ref, n_ref, *, chunk, scale):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    q = q_ref[0].astype(jnp.float32) * scale      # (L, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    li = jnp.minimum(ig_ref[0], I_CAP)            # (L,)
    lf = jax.nn.log_sigmoid(fg_ref[0])
    a = jnp.cumsum(lf)                            # (L,)
    a_l = a[-1]

    dec_q = jnp.exp(a)[:, None]                   # (L, 1)
    w_kj = jnp.exp(li - a)[:, None]               # i_j * exp(-A_j)

    c = c_ref[...]
    n = n_ref[...]
    out = jax.lax.dot_general(q * dec_q, c, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    den = jax.lax.dot_general(q * dec_q, n[:, None],
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)[:, 0]

    s = jax.lax.dot_general(q * dec_q, k * w_kj, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    s = jnp.where(jj <= ii, s, 0.0)
    out = out + jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    den = den + jnp.sum(s, axis=1)
    h = out / jnp.maximum(jnp.abs(den), 1.0)[:, None]
    out_ref[0] = h.astype(out_ref.dtype)

    w_c = jnp.exp(a_l - a + li)[:, None]          # (L, 1)
    c_ref[...] = c * jnp.exp(a_l) + jax.lax.dot_general(
        k * w_c, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_ref[...] = n * jnp.exp(a_l) + jnp.sum(k * w_c, axis=0)


def mlstm_chunkwise(q, k, v, i_raw, f_raw, *, chunk=128, interpret=False):
    """q,k,v (BH, S, hd); gates (BH, S) fp32 -> h (BH, S, hd).

    S must be divisible by ``chunk`` (ops.py pads)."""
    bh, s, hd = q.shape
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    scale = 1.0 / math.sqrt(hd)

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, scale=scale),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),
            pltpu.VMEM((hd,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, i_raw, f_raw)
