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
    causal_slice: bool = False,   # §Perf: triangle slicing (unrolled path)
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

    if pctx.get_unroll():
        outs = []
        for i in range(n_chunks):
            qpos = q_offset + i * chunk_q + jnp.arange(chunk_q)
            if causal_slice and causal and window == 0:
                # causal triangle: chunk i only attends keys < chunk end
                # (the jnp analogue of the flash kernel's block skipping;
                # saves ~half the attention flops + masked-softmax work)
                hi = min(q_offset + (i + 1) * chunk_q, sk)
                ki, vi = k[:, :hi], v[:, :hi]
                m = mask_for(qpos)[:, :hi]
                outs.append(attend_chunk(qc[i], ki, vi, m, scale))
            else:
                outs.append(attend_chunk(qc[i], k, v, mask_for(qpos),
                                         scale))
        out = jnp.stack(outs)
    else:
        def body(_, args):
            i, qi = args
            qpos = q_offset + i * chunk_q + jnp.arange(chunk_q)
            return None, attend_chunk(qi, k, v, mask_for(qpos), scale)

        _, out = jax.lax.scan(body, None, (jnp.arange(n_chunks), qc))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


def decode_attention_sp(q, k_cache, v_cache, cache_len) -> jnp.ndarray:
    """Flash-decoding over the sequence-sharded KV cache (§Perf
    sp_decode): an explicit shard_map keeps each chip's cache shard in
    place — local partial softmax (max-trick) + tiny psum of (m, l, o)
    over the ``model`` axis — instead of GSPMD's whole-cache re-gather
    to kv-head sharding each layer.

    q (B,1,H,hd); caches (B,S,Hkv,hd) with S sharded over 'model' and B
    over DP axes; cache_len scalar."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel import ctx as pctx

    mesh = pctx.get_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return decode_attention(q, k_cache, v_cache, cache_len)
    m = mesh.shape["model"]
    b, s = q.shape[0], k_cache.shape[1]
    ba = pctx.batch_axes(mesh)
    dp = pctx.dp_size(mesh)
    bspec = ((ba if len(ba) > 1 else ba[0])
             if (dp > 1 and b % dp == 0) else None)
    s_loc = s // m

    def local_fn(ql, kl, vl, ln):
        # shard offset along the sequence axis
        rank = jax.lax.axis_index("model")
        base = rank * s_loc
        hkv = kl.shape[2]
        h = ql.shape[2]
        kl = _repeat_kv(kl, h // hkv)
        vl = _repeat_kv(vl, h // hkv)
        scale = 1.0 / jnp.sqrt(jnp.float32(ql.shape[-1]))
        sc = jnp.einsum("bqhd,bkhd->bhk", ql.astype(jnp.float32) * scale,
                        kl.astype(jnp.float32))          # (B,H,s_loc)
        valid = (base + jnp.arange(s_loc))[None, None, :] < ln
        sc = jnp.where(valid, sc, NEG_INF)
        m_loc = jnp.max(sc, axis=-1)                      # (B,H)
        m_g = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(sc - m_g[..., None])
        p = jnp.where(valid, p, 0.0)
        l_loc = jnp.sum(p, axis=-1)                       # (B,H)
        o_loc = jnp.einsum("bhk,bkhd->bhd", p,
                           vl.astype(jnp.float32))        # (B,H,hd)
        l_g = jax.lax.psum(l_loc, "model")
        o_g = jax.lax.psum(o_loc, "model")
        o = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return o[:, None].astype(ql.dtype)                # (B,1,H,hd)

    ln = jnp.asarray(cache_len).reshape(())
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, "model", None, None),
                  P(bspec, "model", None, None), P()),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, ln)


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
