"""Latent attention (MLA) of the DeepSeek-V2/V3 family (Moonlight).

Per layer, on the normed input ``h`` (``q_lora_rank`` null: the query
is one projection)::

    [q_nope ‖ q_pe] = h @ wq                    (B, S, H, nope + rope)
    [c_kv ‖ k_pe]   = h @ wkv_a                 (B, S, R + P)
    c_kv            = rms_norm(c_kv, kv_norm)
    q_pe, k_pe      = rope(q_pe), rope(k_pe)    k_pe shared by every head
    [k_nope ‖ v]    = c_kv @ wkv_b              (B, S, H, nope + v)
    o = softmax([q_nope ‖ q_pe] . [k_nope ‖ k_pe] * (nope + rope) ** -0.5) v
    out             = o @ wo

RoPE follows the published modelling code, which rotates the rope
features in (even, odd) pairs: it de-interleaves them, then rotates
halves; the frequencies are ``theta ** (-2i / rope)``.

What a position leaves in the cache is its latent row: ``c_kv`` (R wide)
and ``k_pe`` (P wide), stacked over layers as ``ckv`` (L, B, T, R) and
``kpe`` (L, B, P, T) (positions minor: the layout the TPU gives a
P = 64 wide row anyway, and the decode kernel's).  The full forward and
the prefill decompress ``c_kv`` through ``wkv_b`` into per-head keys
and values.  Decode never does: the key half of ``wkv_b`` is absorbed
into the query and the value half applied after the weighted sum, so
every head attends the latent rows themselves, one KV head of
``[c_kv ‖ k_pe]`` with values ``c_kv``: on a TPU the named Pallas kernel
``kernels/mla_decode.py``, which reads the layer's rows in place,
elsewhere ``attention.decode_attention``, which the kernel equals.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.mla_decode import mla_decode_attention
from repro.models import attention as attn
from repro.models import common as cm
from repro.models.common import ModelConfig

CACHE_BLOCK = 128       # the cache's length is a multiple of the decode block


def _dims(cfg: ModelConfig):
    return (cfg.d_model, cfg.n_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim)


def scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def cache_len(max_len: int) -> int:
    return -(-max_len // CACHE_BLOCK) * CACHE_BLOCK


def params(keys, cfg: ModelConfig) -> dict:
    """The layer's attention weights, drawn from ``keys[0]``."""
    d, h, nope, rope, r, v = _dims(cfg)
    dt = cfg.dtype
    ks = jax.random.split(keys[0], 4)
    return {
        "wq": cm.dense_init(ks[0], (d, h, nope + rope), dt),
        "wkv_a": cm.dense_init(ks[1], (d, r + rope), dt),
        "kv_norm": jnp.zeros((r,), dt),
        "wkv_b": cm.dense_init(ks[2], (r, h, nope + v), dt),
        "wo": cm.dense_init(ks[3], (h, v, d), dt, in_axis=(0, 1)),
    }


def specs(cfg: ModelConfig) -> dict:
    d, h, nope, rope, r, v = _dims(cfg)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, cfg.dtype)
    return {"wq": s(d, h, nope + rope), "wkv_a": s(d, r + rope),
            "kv_norm": s(r), "wkv_b": s(r, h, nope + v), "wo": s(h, v, d)}


def axes(cfg: ModelConfig) -> dict:
    return {"wq": ("embed", "heads", None), "wkv_a": ("embed", None),
            "kv_norm": (None,), "wkv_b": (None, "heads", None),
            "wo": ("heads", None, "embed")}


def _rope(x, positions, theta):
    """x (B, S, heads, P): (even, odd) feature pairs rotated."""
    b, s, n, p = x.shape
    x = x.reshape(b, s, n, p // 2, 2).swapaxes(-1, -2).reshape(b, s, n, p)
    return cm.apply_rope(x, positions, theta)


def _project(cfg: ModelConfig, p: dict, h, positions):
    """The queries and the latent rows of the positions in ``h``:
    q_nope (B,S,H,nope), q_pe (B,S,H,rope), c_kv (B,S,R), k_pe (B,S,P)."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    kv = jnp.dot(h, p["wkv_a"])
    c = cm.rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    q_pe = _rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = _rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0]
    return q[..., :nope], q_pe, c, k_pe


def attend(cfg: ModelConfig, p: dict, h, positions):
    """Causal latent attention of ``h`` (B, S, D) over itself, keys and
    values decompressed.  Returns (output (B, S, D), the layer's cache
    rows ``{"ckv": c_kv (B, S, R), "kpe": k_pe (B, P, S)}``)."""
    b, s, _ = h.shape
    nope, heads = cfg.qk_nope_head_dim, cfg.n_heads
    q_nope, q_pe, c, k_pe = _project(cfg, p, h, positions)
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"])
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k_pe_h = jnp.broadcast_to(k_pe[:, :, None], (b, s, heads,
                                                 k_pe.shape[-1]))
    k = jnp.concatenate([kv[..., :nope], k_pe_h], axis=-1)
    # query chunks of at most 128 rows bound the float32 scores
    chunk = s if s <= 128 else math.gcd(s, 128)
    o = attn.multi_head_attention(q, k, kv[..., nope:], causal=True,
                                  chunk_q=chunk, scale=scale(cfg))
    return (jnp.einsum("bshv,hvd->bsd", o, p["wo"]),
            {"ckv": c, "kpe": k_pe.swapaxes(1, 2)})


def write(cache: dict, rows: dict, layer, pos) -> dict:
    """``rows`` (as ``attend`` returns them) into layer ``layer`` of the
    stacked caches, from position ``pos`` on."""
    return {"ckv": jax.lax.dynamic_update_slice(
                cache["ckv"], rows["ckv"][None], (layer, 0, pos, 0)),
            "kpe": jax.lax.dynamic_update_slice(
                cache["kpe"], rows["kpe"][None], (layer, 0, 0, pos))}


def _latent_attention(q_lat, q_pe, ckv, kpe, layer, length, sc):
    """q_lat (B, H, R), q_pe (B, H, P) over layer ``layer`` of the stacked
    caches, positions ``< length``: (B, H, R)."""
    if jax.default_backend() == "tpu":
        return mla_decode_attention(q_lat, q_pe, ckv, kpe, layer, length,
                                    scale=sc)
    c, k_pe = ckv[layer], kpe[layer].swapaxes(1, 2)
    q = jnp.concatenate([q_lat, q_pe], -1)[:, None]
    k = jnp.concatenate([c, k_pe], -1)[:, :, None]
    o = attn.decode_attention(q, k, c[:, :, None], length, scale=sc)
    return o[:, 0].astype(q_lat.dtype)


def decode(cfg: ModelConfig, p: dict, h, cache: dict, layer, pos):
    """One new position per sequence: ``h`` (B, 1, D) at position
    ``pos``.  Writes its latent row into layer ``layer`` of the stacked
    caches, attends positions ``0..pos`` in latent space, and returns
    (output (B, 1, D), cache)."""
    nope = cfg.qk_nope_head_dim
    q_nope, q_pe, c, k_pe = _project(cfg, p, h, jnp.reshape(pos, (1,)))
    cache = write(cache, {"ckv": c, "kpe": k_pe.swapaxes(1, 2)}, layer, pos)
    w_uk, w_uv = p["wkv_b"][..., :nope], p["wkv_b"][..., nope:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
    o_lat = _latent_attention(q_lat, q_pe[:, 0], cache["ckv"], cache["kpe"],
                              layer, pos + 1, scale(cfg))
    o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
    return jnp.einsum("bhv,hvd->bd", o, p["wo"])[:, None], cache


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Latent rows ``(L, B, T, R)`` and rope keys ``(L, B, P, T)``, ``T``
    the kernel's block multiple (``cache_len``)."""
    t, lb = cache_len(max_len), (cfg.n_layers, batch)
    return {"ckv": jax.ShapeDtypeStruct(lb + (t, cfg.kv_lora_rank),
                                        cfg.dtype),
            "kpe": jax.ShapeDtypeStruct(lb + (cfg.qk_rope_head_dim, t),
                                        cfg.dtype)}


def cache_axes(cfg: ModelConfig) -> dict:
    return {"ckv": ("layer", "batch", "kv_seq", None),
            "kpe": ("layer", "batch", None, "kv_seq")}
