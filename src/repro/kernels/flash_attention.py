"""Blockwise (flash) attention Pallas TPU kernel.

TPU-native adaptation: VMEM-resident (block_q x head_dim) query tiles and
(block_k x head_dim) key/value tiles feed the MXU via
``jax.lax.dot_general`` with fp32 accumulation; the online-softmax
running max/denominator live in VMEM scratch across the (innermost,
``arbitrary``) key-block grid dimension.  Tile sides default to 128/512 —
multiples of the 128-lane MXU dimension.

Supports causal masking, sliding-window (local) attention, and GQA: the
kernel is written over flattened (B*H, S, hd) queries with the k/v
BlockSpec index map folding q-head -> kv-head (h // q_per_kv), so no KV
replication ever materializes in HBM.

Block-level early-exit: key blocks wholly outside the causal/window
band are skipped via ``pl.when`` (the classic flash-attention triangle
saving ~2x on causal, much more for small windows).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, window, block_q, block_k, nk, seq_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_first = iq * block_q                 # first q position in tile
    q_last = q_first + block_q - 1
    k_first = ik * block_k
    k_last = k_first + block_k - 1

    run = k_first < seq_k                  # padded tail key blocks
    if causal:
        run &= k_first <= q_last
    if window > 0:
        run &= k_last > q_first - window

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)                 # (bk, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_flat(q, k, v, *, causal=True, window=0,
                         block_q=128, block_k=512, interpret=False):
    """q (BH, Sq, hd); k/v (BHkv, Sk, hd).  BH % BHkv == 0."""
    bh, sq, hd = q.shape
    bhkv, sk, _ = k.shape
    assert bh % bhkv == 0
    q_per_kv = bh // bhkv
    scale = 1.0 / math.sqrt(hd)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    sq_pad = pl.cdiv(sq, block_q) * block_q
    sk_pad = pl.cdiv(sk, block_k) * block_k
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0)))
    nq = sq_pad // block_q
    nk = sk_pad // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, seq_k=sk)

    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, qpk=q_per_kv: (b // qpk, j, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, i, j, qpk=q_per_kv: (b // qpk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq, :]
