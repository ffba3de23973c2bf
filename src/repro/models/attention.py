"""Reference attention used by every attention-bearing architecture.

This is the pure-jnp path that the dry-run lowers (XLA fuses it well and it
keeps multi-device compiles robust).  The Pallas kernels in
``repro.kernels.flash_attention`` / ``decode_attention`` are numerical
drop-ins validated against this module.

Key property: queries are processed in chunks via ``lax.scan`` (native
flash-style blocking at the HLO level), so a 32k×32k attention never
materializes an (S, S) score tensor — per-chunk memory is (chunk, S).

Grouped-query attention: prefill and training repeat each KV head to its
query heads (``_repeat_kv``).  Single-token decode does not: it contracts
each KV head with its group of query heads, so every step reads the KV
cache once and writes no repeated copy of it (see ``decode_attention``).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jnp.ndarray, q_per_kv: int) -> jnp.ndarray:
    """(B, S, Hkv, hd) -> (B, S, Hkv*q_per_kv, hd) by head-group broadcast."""
    if q_per_kv == 1:
        return k
    b, s, hkv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, q_per_kv, hd))
    return k.reshape(b, s, hkv * q_per_kv, hd)


def attend_chunk(q, k, v, mask, scale):
    """q (B,Cq,H,hd)  k/v (B,Sk,H,hd)  mask (Cq,Sk) bool -> (B,Cq,H,hd)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def multi_head_attention(
    q: jnp.ndarray,               # (B, Sq, H, hd)
    k: jnp.ndarray,               # (B, Sk, Hkv, hd)
    v: jnp.ndarray,               # (B, Sk, Hkv, hd)
    *,
    causal: bool = True,
    window: int = 0,              # 0 = full; >0 = sliding local window
    q_offset: int = 0,            # absolute position of q[0] (for decode)
    chunk_q: int = 1024,
    scale: Optional[float] = None,  # default head_dim ** -0.5
) -> jnp.ndarray:
    """Chunked masked attention.  Handles GQA by repeating KV heads.  The
    values may be narrower than the keys (latent attention's decompressed
    heads); the output has the values' width."""
    b, sq, h, hd = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    q_per_kv = h // k.shape[2]
    k = _repeat_kv(k, q_per_kv)
    v = _repeat_kv(v, q_per_kv)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    kpos = jnp.arange(sk)

    def mask_for(qpos):
        m = jnp.ones((qpos.shape[0], sk), dtype=bool)
        if causal:
            m &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            m &= kpos[None, :] > qpos[:, None] - window
        return m

    if sq <= chunk_q:
        qpos = q_offset + jnp.arange(sq)
        return attend_chunk(q, k, v, mask_for(qpos), scale)

    n_chunks = sq // chunk_q
    assert sq % chunk_q == 0, f"sq={sq} not divisible by chunk_q={chunk_q}"
    qc = q.reshape(b, n_chunks, chunk_q, h, hd).transpose(1, 0, 2, 3, 4)

    from repro.parallel import ctx as pctx

    def chunk(i, qi):
        qpos = q_offset + i * chunk_q + jnp.arange(chunk_q)
        return attend_chunk(qi, k, v, mask_for(qpos), scale)

    if pctx.get_unroll():
        out = jnp.stack([chunk(i, qc[i]) for i in range(n_chunks)])
    else:
        _, out = jax.lax.scan(lambda _, a: (None, chunk(*a)), None,
                              (jnp.arange(n_chunks), qc))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


def decode_attention(
    q: jnp.ndarray,               # (B, 1, H, hd)
    k_cache: jnp.ndarray,         # (B, S, Hkv, hd)
    v_cache: jnp.ndarray,         # (B, S, Hkv, hd)
    cache_len: jnp.ndarray | int, # valid prefix length (scalar or (B,))
    scale: Optional[float] = None,  # default head_dim ** -0.5
) -> jnp.ndarray:
    """Single-token attention against a (possibly padded) KV cache.

    Each KV head is contracted with its group of ``G = H // Hkv`` query
    heads (query head ``h`` belongs to KV head ``h // G``, the layout of
    ``_repeat_kv``), so the cache is read once, in place.  Repeating the
    cache to ``H`` heads first would write ``G`` copies of it every step,
    and with one query position the per-head products lower to
    elementwise multiply-reduces over that copy instead of matrix
    products.  Same precision as the repeated form: operands in their
    own dtype, scores and softmax in float32, probabilities cast to the
    cache dtype for the product with V; for ``G = 1`` it is the same
    contraction.  The values may be narrower than the keys: latent
    attention's absorbed decode (one KV head of ``[c_kv ‖ k_pe]``, values
    ``c_kv``, scale from the decompressed head width) is this contraction,
    and the oracle of ``kernels/mla_decode.py``."""
    b, _, h, hd = q.shape
    sk, hkv, dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[-1]
    qg = q.reshape(b, hkv, h // hkv, hd)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * scale
    kpos = jnp.arange(sk)
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        valid = jnp.broadcast_to(kpos[None, :] < cache_len, (b, sk))
    else:
        valid = kpos[None, :] < cache_len[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # the (g, k) output order lowers P.V as the repeated form lowered it,
    # so G = 1 gives the same bits; the TPU compiler folds the transpose
    o = jnp.einsum("bkgs,bskd->bgkd", p.astype(v_cache.dtype), v_cache)
    return o.transpose(0, 2, 1, 3).reshape(b, 1, h, dv)
