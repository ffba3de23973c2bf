"""Grouped-query attention (GQA) of the dense and expert decoders.

Per layer, on the normed input ``h``::

    q = h @ wq                       (B, S, H, hd)
    k, v = h @ wk, h @ wv            (B, S, Hkv, hd)
    q, k = qk_norm(q), qk_norm(k)    (``cfg.qk_norm``)
    q, k = rope(q), rope(k)
    o = softmax(q . k / sqrt(hd)) v  each KV head shared by its group
                                     of H / Hkv query heads
    out = o @ wo

What a position leaves in the cache is its key and value, stacked over
layers as ``k`` and ``v`` (L, B, Hkv, T, hd): each (sequence, KV head)'s
positions are one contiguous (T, hd) matrix, as
``attention.decode_attention`` reads them, with ``T`` the positions
rounded up to 16 (``cache_rows``).  The carried cache is kept row-major
(``_row_major``), so a decode step writes its row in place and the
attention's products read their layer straight out of the stack.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models.common import ModelConfig
from repro.parallel import ctx as pctx


def params(keys, cfg: ModelConfig) -> dict:
    """The layer's attention weights, drawn from ``keys[0..3]``."""
    d, h, hkv, hd, dt = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                         cfg.dtype)
    p = {
        "wq": cm.dense_init(keys[0], (d, h, hd), dt),
        "wk": cm.dense_init(keys[1], (d, hkv, hd), dt),
        "wv": cm.dense_init(keys[2], (d, hkv, hd), dt),
        "wo": cm.dense_init(keys[3], (h, hd, d), dt, in_axis=(0, 1)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def specs(cfg: ModelConfig) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s = lambda *shape: jax.ShapeDtypeStruct(shape, cfg.dtype)
    p = {"wq": s(d, h, hd), "wk": s(d, hkv, hd), "wv": s(d, hkv, hd),
         "wo": s(h, hd, d)}
    if cfg.qk_norm:
        p["q_norm"] = s(hd)
        p["k_norm"] = s(hd)
    return p


def axes(cfg: ModelConfig) -> dict:
    p = {"wq": ("embed", "heads", None), "wk": ("embed", "kv", None),
         "wv": ("embed", "kv", None), "wo": ("heads", None, "embed")}
    if cfg.qk_norm:
        p["q_norm"] = (None,)
        p["k_norm"] = (None,)
    return p


def _project(cfg: ModelConfig, p: dict, h, positions):
    """q (B,S,H,hd), k and v (B,S,Hkv,hd) of the positions in ``h``."""
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    if cfg.qk_norm:
        q = cm.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = cm.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    k = cm.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attend(cfg: ModelConfig, p: dict, h, positions):
    """Causal attention of ``h`` (B, S, D) over itself (within
    ``cfg.window`` when set).  Returns (output (B, S, D), the layer's
    cache rows ``{"k", "v"}``, each (B, Hkv, S, hd))."""
    q, k, v = _project(cfg, p, h, positions)
    o = attn.multi_head_attention(q, k, v, causal=True, window=cfg.window)
    return (jnp.einsum("bshk,hkd->bsd", o, p["wo"]),
            {"k": k.swapaxes(1, 2), "v": v.swapaxes(1, 2)})


def write(cache: dict, rows: dict, layer, pos) -> dict:
    """``rows`` (as ``attend`` returns them) into layer ``layer`` of the
    stacked cache, from position ``pos`` on."""
    at = (layer, 0, 0, pos, 0)
    return {n: _row_major(jax.lax.dynamic_update_slice(
        cache[n], rows[n][None], at)) for n in ("k", "v")}


def decode(cfg: ModelConfig, p: dict, h, cache: dict, layer, pos):
    """One new position per sequence: ``h`` (B, 1, D) at position
    ``pos``.  Writes its key and value into layer ``layer`` of the
    stacked cache, attends positions ``0..pos`` of that layer in place,
    and returns (output (B, 1, D), cache)."""
    q, k, v = _project(cfg, p, h, jnp.reshape(pos, (1,)))
    cache = write(cache, {"k": k.swapaxes(1, 2), "v": v.swapaxes(1, 2)},
                  layer, pos)
    # this layer as (B, T, Hkv, hd); the products read it in place
    kl, vl = (jax.lax.dynamic_index_in_dim(cache[n], layer, keepdims=False)
              .swapaxes(1, 2) for n in ("k", "v"))
    o = attn.decode_attention(q, kl, vl, pos + 1)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), cache


def cache_rows(max_len: int) -> int:
    """Positions a cache of ``max_len`` holds: rounded up to the 16 rows
    of a bfloat16 (16, 128) tile, so that the TPU's own layout of the
    cache is row-major, the layout ``decode`` keeps it in (at other
    lengths the TPU lays it out otherwise, and the step copies it
    whole)."""
    return -(-max_len // 16) * 16


def _row_major(cache: jnp.ndarray) -> jnp.ndarray:
    """Keep the carried (L, B, Hkv, T, hd) stack row-major.  Left to
    itself the TPU compiler lays the loop's cache out position-major for
    the row write, and then either copies the whole cache in and out of
    the step or relays out each layer for the attention's products; in
    row-major the row is written in place and the products read their
    layer straight out of the stack.  On a mesh the stack is left as it
    is: the partitioner cannot split a layout constraint, and would
    gather the whole cache onto every device."""
    from jax.experimental.layout import Layout, with_layout_constraint

    if pctx.get_mesh() is not None:
        return cache
    return with_layout_constraint(
        cache, Layout(major_to_minor=tuple(range(cache.ndim))))


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    shp = (cfg.n_layers, batch, cfg.n_kv_heads, cache_rows(max_len), cfg.hd)
    return {"k": jax.ShapeDtypeStruct(shp, cfg.dtype),
            "v": jax.ShapeDtypeStruct(shp, cfg.dtype)}


def cache_axes(cfg: ModelConfig) -> dict:
    # the sequence takes the model axis; a "kv" name on the heads,
    # which come first, would take it instead
    ax = ("layer", "batch", None, "kv_seq", None)
    return {"k": ax, "v": ax}
