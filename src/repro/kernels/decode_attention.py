"""Flash-decoding Pallas TPU kernel: one new token vs. a long KV cache.

Decode attention is memory-bound: the whole KV cache streams HBM->VMEM
once per step.  The kernel tiles the cache sequence dimension (grid dim
``arbitrary``) with online-softmax scratch, processing all q heads of one
batch element per grid row so each KV tile is read ONCE for the whole
GQA head group (kv reuse = q_per_kv — the roofline win vs. naive).

Layouts: q (B, H, hd); k/v caches (B, S, Hkv, hd); per-batch valid
``lengths`` mask ragged caches.  Block: (block_s x hd) KV tiles, fp32
accumulation (H x hd) in VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, block_s, ns, q_per_kv):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[i]
    s_first = j * block_s

    @pl.when(s_first < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale       # (H, hd)
        k = k_ref[0].astype(jnp.float32)               # (bs, Hkv, hd)
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        bs, hkv, _ = k.shape
        # scores: q head hq attends kv head hq // q_per_kv
        qg = q.reshape(hkv, q_per_kv, hd)
        s = jnp.einsum("ghd,sgd->ghs", qg, k,
                       preferred_element_type=jnp.float32)  # (Hkv,qpk,bs)
        s = s.reshape(h, bs)
        kpos = s_first + jax.lax.broadcasted_iota(jnp.int32, (h, bs), 1)
        mask = kpos < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                            # (H,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)  # (H, bs)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        m_ref[...] = m_new
        pg = p.reshape(hkv, q_per_kv, bs)
        pv = jnp.einsum("gqs,sgd->gqd", pg, v,
                        preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv.reshape(h, hd)

    @pl.when(j == ns - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *, block_s=512,
                     interpret=False):
    """q (B, H, hd); k/v (B, S, Hkv, hd); lengths (B,) int32."""
    b, h, hd = q.shape
    _, s, hkv, _ = k_cache.shape
    q_per_kv = h // hkv
    scale = 1.0 / math.sqrt(hd)
    block_s = min(block_s, s)
    s_pad = pl.cdiv(s, block_s) * block_s
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
    ns = s_pad // block_s

    kernel = functools.partial(_kernel, scale=scale, block_s=block_s,
                               ns=ns, q_per_kv=q_per_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, ns),
        in_specs=[
            pl.BlockSpec((1, h, hd), lambda i, j, lens: (i, 0, 0)),
            pl.BlockSpec((1, block_s, hkv, hd),
                         lambda i, j, lens: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, hkv, hd),
                         lambda i, j, lens: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, j, lens: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, hd), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)
