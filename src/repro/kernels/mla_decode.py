"""Latent-attention (MLA) decode as a Pallas TPU kernel: one new token of
each sequence against its layer's compressed cache.

With the key up-projection absorbed into the query, every query head of
a DeepSeek-style latent attention attends the same cached key
``[c_kv ‖ k_pe]`` (one KV head shared by all heads) and its value is
``c_kv``.  The kernel reads the layer's rows straight out of the stacked
caches, the layer picked by a prefetched scalar, so no slice of a cache
is copied: one block of ``block_t`` positions of up to 16 sequences per
grid step, online softmax across the position blocks.  Blocks wholly
past the valid length are neither fetched (their index is clamped to the
last valid block, and a fetch whose block index repeats is skipped) nor
computed.

Layouts, all row-major so that the TPU's own layout of each cache is the
kernel's and no operand is relaid out: q_lat (B, H, R), q_pe (B, H, P);
c_kv cache (L, B, T, R); k_pe cache (L, B, P, T), positions minor, since
a minor axis of P = 64 would make the TPU lay out positions minor anyway;
T a multiple of ``block_t``; out (B, H, R).  Scores and softmax in
float32, the unnormalised probabilities cast to the cache dtype for the
product with the values; ``models.attention.decode_attention`` over the
concatenated key is the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _kernel(layer_ref, len_ref, ql_ref, qp_ref, c_ref, p_ref, o_ref, acc_ref,
            m_ref, l_ref, *, scale, block_t, n_t):
    del layer_ref                       # used by the index maps only
    j = pl.program_id(1)
    length = len_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * block_t < length)
    def _compute():
        h = ql_ref.shape[1]
        kpos = j * block_t + jax.lax.broadcasted_iota(jnp.int32,
                                                      (h, block_t), 1)
        valid = kpos < length
        for r in range(ql_ref.shape[0]):
            c = c_ref[0, r]                                   # (bt, R)
            s = jax.lax.dot_general(
                ql_ref[r], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # (H, bt)
            s = (s + jnp.dot(qp_ref[r], p_ref[0, r],
                             preferred_element_type=jnp.float32)) * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[r]                                 # (H, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[r] = l_ref[r] * corr + jnp.sum(p, axis=1, keepdims=True)
            m_ref[r] = m_new
            pv = jnp.dot(p.astype(c.dtype), c,
                         preferred_element_type=jnp.float32)  # (H, R)
            acc_ref[r] = acc_ref[r] * corr[:, :1] + pv

    @pl.when(j == n_t - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...][:, :, :1]
                      ).astype(o_ref.dtype)


def _batch_block(b: int, cap: int = 16) -> int:
    return max(d for d in range(1, min(b, cap) + 1) if b % d == 0)


def mla_decode_attention(q_lat, q_pe, ckv, kpe, layer, length, *,
                         scale: float, block_t: int = 128,
                         interpret: bool = False):
    """q_lat (B, H, R), q_pe (B, H, P); ckv (L, B, T, R), kpe (L, B, P, T);
    ``layer`` and ``length`` int32 scalars (positions ``< length`` are
    attended).  Returns (B, H, R): the probabilities times ``c_kv``."""
    b, h, r = q_lat.shape
    p = q_pe.shape[-1]
    t = ckv.shape[2]
    block_t = min(block_t, t)
    if t % block_t:
        raise ValueError(f"cache length {t} is not a multiple of the "
                         f"block {block_t}")
    n_t = t // block_t
    bb = _batch_block(b)

    def last(len_ref):
        return jnp.minimum(jnp.maximum(len_ref[0] - 1, 0) // block_t,
                           n_t - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b // bb, n_t),
        in_specs=[
            pl.BlockSpec((bb, h, r), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((bb, h, p), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, bb, block_t, r), lambda i, j, lr, nr: (
                lr[0], i, jnp.minimum(j, last(nr)), 0)),
            pl.BlockSpec((1, bb, p, block_t), lambda i, j, lr, nr: (
                lr[0], i, 0, jnp.minimum(j, last(nr)))),
        ],
        out_specs=pl.BlockSpec((bb, h, r), lambda i, j, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((bb, h, r), jnp.float32),
            pltpu.VMEM((bb, h, LANES), jnp.float32),
            pltpu.VMEM((bb, h, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, block_t=block_t,
                               n_t=n_t)
    as_vec = lambda v: jnp.reshape(jnp.asarray(v, jnp.int32), (1,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode_attention",
    )(as_vec(layer), as_vec(length), q_lat, q_pe, ckv, kpe)
