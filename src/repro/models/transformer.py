"""Dense decoder-only transformer family.

Covers: phi3-medium-14b, glm4-9b, deepseek-coder-33b, qwen3-4b (qk_norm),
pixtral-12b backbone (patch-embedding frontend stub), and the
recurrentgemma / MoE families reuse its attention + embedding pieces.

Layer: pre-RMSNorm -> GQA attention (RoPE, optional QK-norm, optional
sliding window) -> residual -> pre-RMSNorm -> SwiGLU MLP -> residual.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import mla
from repro.models.common import ModelConfig

# ---------------------------------------------------------------------------
# Per-layer params
# ---------------------------------------------------------------------------


def _layer_init(cfg: ModelConfig, moe_ffn: bool):
    d, h, hkv, hd, ff, dt = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.hd, cfg.d_ff, cfg.dtype)

    def init_one(key):
        ks = jax.random.split(key, 8)
        if cfg.kv_lora_rank:
            p = {"ln1": jnp.zeros((d,), dt), **mla.params(ks[0], cfg),
                 "ln2": jnp.zeros((d,), dt)}
        else:
            p = {
                "ln1": jnp.zeros((d,), dt),
                "wq": cm.dense_init(ks[0], (d, h, hd), dt),
                "wk": cm.dense_init(ks[1], (d, hkv, hd), dt),
                "wv": cm.dense_init(ks[2], (d, hkv, hd), dt),
                "wo": cm.dense_init(ks[3], (h, hd, d), dt, in_axis=(0, 1)),
                "ln2": jnp.zeros((d,), dt),
            }
        if moe_ffn:
            from repro.models import moe

            p["moe"] = moe.moe_params(ks[4], cfg)
        else:
            p["mlp"] = cm.mlp_params(ks[4], d, ff, dt)
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros((hd,), dt)
            p["k_norm"] = jnp.zeros((hd,), dt)
        return p

    return init_one


def _layer_specs(cfg: ModelConfig, moe_ffn: bool) -> dict:
    d, h, hkv, hd, ff, dt = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.hd, cfg.d_ff, cfg.dtype)
    if cfg.kv_lora_rank:
        p = {"ln1": jax.ShapeDtypeStruct((d,), dt), **mla.specs(cfg),
             "ln2": jax.ShapeDtypeStruct((d,), dt)}
    else:
        p = {
            "ln1": jax.ShapeDtypeStruct((d,), dt),
            "wq": jax.ShapeDtypeStruct((d, h, hd), dt),
            "wk": jax.ShapeDtypeStruct((d, hkv, hd), dt),
            "wv": jax.ShapeDtypeStruct((d, hkv, hd), dt),
            "wo": jax.ShapeDtypeStruct((h, hd, d), dt),
            "ln2": jax.ShapeDtypeStruct((d,), dt),
        }
    if moe_ffn:
        from repro.models import moe

        p["moe"] = moe.moe_specs(cfg)
    else:
        p["mlp"] = cm.mlp_specs(d, ff, dt)
    if cfg.qk_norm:
        p["q_norm"] = jax.ShapeDtypeStruct((hd,), dt)
        p["k_norm"] = jax.ShapeDtypeStruct((hd,), dt)
    return p


def _layer_axes(cfg: ModelConfig, moe_ffn: bool) -> dict:
    if cfg.kv_lora_rank:
        p = {"ln1": (None,), **mla.AXES, "ln2": (None,)}
    else:
        p = {
            "ln1": (None,),
            "wq": ("embed", "heads", None),
            "wk": ("embed", "kv", None),
            "wv": ("embed", "kv", None),
            "wo": ("heads", None, "embed"),
            "ln2": (None,),
        }
    if moe_ffn:
        from repro.models import moe

        p["moe"] = moe.moe_axes(cfg)
    else:
        p["mlp"] = dict(cm.MLP_AXES)
    if cfg.qk_norm:
        p["q_norm"] = (None,)
        p["k_norm"] = (None,)
    return p


def _groups(cfg: ModelConfig):
    """(group, layers, expert FFN) of each stacked layer group the model
    has, in the order they run: the leading dense layers
    (``first_k_dense``, a SwiGLU of width ``d_ff``), then the rest
    (experts when ``n_experts``)."""
    k = cfg.first_k_dense
    out = [("dense_layers", k, False)] if k else []
    return out + [("layers", cfg.n_layers - k, cfg.n_experts > 0)]


# ---------------------------------------------------------------------------
# Top-level params
# ---------------------------------------------------------------------------


def init(cfg: ModelConfig, key) -> dict:
    k_emb, k_layers, k_head, k_fe = jax.random.split(key, 4)
    params = {
        "embed": cm.embed_init(k_emb, (cfg.vocab, cfg.d_model), cfg.dtype),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.dtype),
        "lm_head": cm.dense_init(k_head, (cfg.d_model, cfg.vocab), cfg.dtype),
    }
    # the last group keeps the layers' key, so a one-group model draws
    # the weights it drew before the dense-then-expert stack existed
    for i, (name, n, moe_ffn) in enumerate(reversed(_groups(cfg))):
        params[name] = cm.stack_layer_params(
            _layer_init(cfg, moe_ffn),
            jax.random.fold_in(k_layers, i) if i else k_layers, n)
    if cfg.frontend:
        params["frontend_proj"] = cm.dense_init(
            k_fe, (cfg.frontend_dim, cfg.d_model), cfg.dtype)
    return params


def param_specs(cfg: ModelConfig) -> dict:
    p = {
        "embed": jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), cfg.dtype),
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), cfg.dtype),
        "lm_head": jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab), cfg.dtype),
    }
    for name, n, moe_ffn in _groups(cfg):
        p[name] = cm.stacked_specs(_layer_specs(cfg, moe_ffn), n)
    if cfg.frontend:
        p["frontend_proj"] = jax.ShapeDtypeStruct(
            (cfg.frontend_dim, cfg.d_model), cfg.dtype)
    return p


def logical_axes(cfg: ModelConfig) -> dict:
    p = {
        "embed": ("vocab", "embed"),
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }
    for name, _, moe_ffn in _groups(cfg):
        p[name] = cm.stacked_axes(_layer_axes(cfg, moe_ffn))
    if cfg.frontend:
        p["frontend_proj"] = (None, "embed")
    return p


# ---------------------------------------------------------------------------
# Forward (training / scoring)
# ---------------------------------------------------------------------------


def tp_attn_weights(cfg: ModelConfig, p: dict):
    """TP-aligned attention weights (cfg.tp_attention, §Perf hillclimb).

    GSPMD cannot propagate head-sharding through the GQA repeat-reshape
    (Hkv x q_per_kv -> H), so the baseline attention einsums replicate
    over the model axis.  This transform (a) repeats the KV projection
    weights to one kv head per q head (identical k/v values per group —
    bitwise the same math) and (b) zero-pads the q/kv/o head dims to a
    multiple of the TP width (padded o-rows are zero, so outputs are
    exactly unchanged).  Returns (wq, wk, wv, wo, h_eff)."""
    from repro.parallel import ctx as pctx

    mesh = pctx.get_mesh()
    wq, wk, wv, wo = p["wq"], p["wk"], p["wv"], p["wo"]
    h = cfg.n_heads
    if not cfg.tp_attention or mesh is None or "model" not in \
            mesh.axis_names:
        return wq, wk, wv, wo, h
    tp = mesh.shape["model"]
    qpk = cfg.q_per_kv
    wk = jnp.repeat(wk, qpk, axis=1)         # one kv head per q head
    wv = jnp.repeat(wv, qpk, axis=1)
    h_eff = -(-h // tp) * tp                 # ceil to TP multiple
    if h_eff != h:
        pad = ((0, 0), (0, h_eff - h), (0, 0))
        wq, wk, wv = (jnp.pad(w, pad) for w in (wq, wk, wv))
        wo = jnp.pad(wo, ((0, h_eff - h), (0, 0), (0, 0)))
    from jax.sharding import PartitionSpec as P

    cst = lambda w, spec: jax.lax.with_sharding_constraint(
        w, jax.sharding.NamedSharding(mesh, spec))
    wq = cst(wq, P(None, "model", None))
    wk = cst(wk, P(None, "model", None))
    wv = cst(wv, P(None, "model", None))
    wo = cst(wo, P("model", None, None))
    return wq, wk, wv, wo, h_eff


def _attn_block(cfg: ModelConfig, p: dict, x: jnp.ndarray,
                positions: jnp.ndarray, *, window: int = 0) -> jnp.ndarray:
    if cfg.kv_lora_rank:
        o, _, _ = mla.attend(cfg, p, cm.rms_norm(x, p["ln1"], cfg.norm_eps),
                             positions)
        return x + o
    wq, wk, wv, wo, _ = tp_attn_weights(cfg, p)
    h = cm.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, wq)
    k = jnp.einsum("bsd,dhk->bshk", h, wk)
    v = jnp.einsum("bsd,dhk->bshk", h, wv)
    if cfg.qk_norm:
        q = cm.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = cm.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    k = cm.apply_rope(k, positions, cfg.rope_theta)
    o = attn.multi_head_attention(q, k, v, causal=True, window=window,
                                  causal_slice=cfg.causal_slice)
    return x + jnp.einsum("bshk,hkd->bsd", o, wo)


def _ffn_block(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    """Returns (x_out, aux_loss)."""
    h = cm.rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        from repro.models import moe

        y, aux = moe.moe_ffn(cfg, p["moe"], h)
        return x + y, aux
    return x + cm.mlp_forward(p["mlp"], h), jnp.float32(0.0)


def embed_tokens(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
                 frontend_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend and frontend_embeds is not None:
        fe = jnp.dot(frontend_embeds.astype(cfg.dtype),
                     params["frontend_proj"])
        nf = fe.shape[1]
        x = jnp.concatenate([fe, x[:, nf:, :]], axis=1)
    return x


def forward(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
            frontend_embeds: Optional[jnp.ndarray] = None,
            return_aux: bool = False):
    """tokens (B, S) -> logits (B, S, V) [+ moe aux loss]."""
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    positions = jnp.arange(tokens.shape[1])

    def body(carry, lp):
        xc, aux = carry
        xc = _attn_block(cfg, lp, xc, positions, window=cfg.window)
        xc, a = _ffn_block(cfg, lp, xc)
        return xc, aux + a

    carry = (x, jnp.float32(0.0))
    for name, _, _ in _groups(cfg):
        carry = cm.scan_layers(body, carry, params[name], cfg)
    x, aux = carry
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x, params["lm_head"]).astype(jnp.float32)
    if return_aux:
        return logits, aux / cfg.n_layers
    return logits


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(cfg: ModelConfig, params: dict, tokens: jnp.ndarray,
            frontend_embeds: Optional[jnp.ndarray] = None,
            max_len: Optional[int] = None) -> Tuple[jnp.ndarray, dict]:
    """Returns (last-position logits (B,V), kv cache).

    cache = {"k": (L,B,Hkv,T,hd), "v": ..., "len": int32[]}: each
    (sequence, KV head)'s positions are one contiguous (T, hd) matrix,
    ``T`` = ``cache_rows(max_len)``, as the decode attention reads them
    (``decode_step``).  ``max_len`` (default S + 64) reserves decode
    headroom.  A latent-attention model caches its latent rows instead
    (``_latent_prefill``).
    """
    if cfg.kv_lora_rank:
        return _latent_prefill(cfg, params, tokens, frontend_embeds,
                               max_len)
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    s = tokens.shape[1]
    positions = jnp.arange(s)

    def body(xc, lp):
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if cfg.qk_norm:
            q = cm.head_rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = cm.head_rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        o = attn.multi_head_attention(q, k, v, causal=True, window=cfg.window)
        xc = xc + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        xc, _ = _ffn_block(cfg, lp, xc)
        return xc, (k.swapaxes(1, 2), v.swapaxes(1, 2))

    fn = cm.maybe_remat(body, cfg)
    x, (ks, vs) = cm.scan_or_unroll(fn, x, params["layers"],
                                    cfg.scan_layers)
    x = cm.rms_norm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x[:, 0, :], params["lm_head"]).astype(jnp.float32)
    cap = cache_rows(max(max_len if max_len is not None else s + 64, s))
    if cap > s:
        pad = ((0, 0), (0, 0), (0, 0), (0, cap - s), (0, 0))
        ks, vs = jnp.pad(ks, pad), jnp.pad(vs, pad)
    cache = {"k": ks, "v": vs, "len": jnp.int32(s)}
    return logits, cache


def cache_rows(max_len: int) -> int:
    """Positions a dense K/V cache of ``max_len`` holds: rounded up to
    the 16 rows of a bfloat16 (16, 128) tile, so that the TPU's own
    layout of the cache is row-major, the layout ``decode_step`` keeps it
    in (at other lengths the TPU lays it out otherwise, and the step
    copies it whole)."""
    return -(-max_len // 16) * 16


def _row_major(cache: jnp.ndarray) -> jnp.ndarray:
    """Keep the carried (L, B, Hkv, T, hd) stack row-major.  Left to
    itself the TPU compiler lays the loop's cache out position-major for
    the row write, and then either copies the whole cache in and out of
    the step or relays out each layer for the attention's products; in
    row-major the row is written in place and the products read their
    layer straight out of the stack."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(
        cache, Layout(major_to_minor=tuple(range(cache.ndim))))


def _pin_seq_sharding(kc: jnp.ndarray, vc: jnp.ndarray):
    """sp_decode (§Perf): constrain the per-layer KV slice to the cache's
    storage layout (sequence over `model`, batch over DP) so the decode
    attention computes flash-decoding style (partial softmax + all-reduce)
    instead of GSPMD resharding the whole cache to kv-head sharding —
    the 'involuntary full rematerialization' the baseline HLO warns about."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.parallel import ctx as pctx

    mesh = pctx.get_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return kc, vc
    ba = pctx.batch_axes(mesh)
    b = kc.shape[0]
    dp = pctx.dp_size(mesh)
    bspec = (ba if len(ba) > 1 else ba[0]) if (b % max(dp, 1) == 0
                                               and dp > 1) else None
    spec = P(bspec, "model", None, None)
    cst = lambda x: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
    return cst(kc), cst(vc)


def _scan_groups(cfg: ModelConfig, params: dict, body, carry):
    """``body(carry, (layer params, layer index)) -> (carry, None)`` over
    every layer, group after group."""
    first = 0
    for name, n, _ in _groups(cfg):
        carry, _ = cm.scan_or_unroll(
            body, carry, (params[name], first + jnp.arange(n)),
            cfg.scan_layers)
        first += n
    return carry


def decode_step(cfg: ModelConfig, params: dict, token: jnp.ndarray,
                cache: dict) -> Tuple[jnp.ndarray, dict]:
    """token (B,) int32; cache from ``prefill``.  One-token step.

    The stacked K/V cache is carried through the layer scan: each layer
    writes only its new row, at ``(layer, len)``, and attends to its own
    layer of the carry, so a caller that donates the cache has it
    updated in place.  Returns (logits (B,V), updated cache)."""
    if cfg.kv_lora_rank:
        return _latent_decode_step(cfg, params, token, cache)
    x = jnp.take(params["embed"], token[:, None], axis=0)  # (B,1,D)
    pos = cache["len"]
    positions = jnp.reshape(pos, (1,))

    def body(carry, layer_in):
        xc, (kc, vc) = carry
        lp, i = layer_in
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if cfg.qk_norm:
            q = cm.head_rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = cm.head_rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        at = (i, 0, 0, pos, 0)
        kc = _row_major(jax.lax.dynamic_update_slice(
            kc, k.swapaxes(1, 2)[None], at))
        vc = _row_major(jax.lax.dynamic_update_slice(
            vc, v.swapaxes(1, 2)[None], at))
        # this layer as (B, T, Hkv, hd); the products read it in place
        kl = jax.lax.dynamic_index_in_dim(kc, i, keepdims=False).swapaxes(1, 2)
        vl = jax.lax.dynamic_index_in_dim(vc, i, keepdims=False).swapaxes(1, 2)
        if cfg.sp_decode:
            kl, vl = _pin_seq_sharding(kl, vl)
            o = attn.decode_attention_sp(q, kl, vl, pos + 1)
        else:
            o = attn.decode_attention(q, kl, vl, pos + 1)
        xc = xc + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        xc, _ = _ffn_block(cfg, lp, xc)
        return (xc, (kc, vc)), None

    x, (ks, vs) = _scan_groups(cfg, params, body,
                               (x, (cache["k"], cache["v"])))
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x[:, 0, :], params["lm_head"]).astype(jnp.float32)
    return logits, {"k": ks, "v": vs, "len": pos + 1}


# ---------------------------------------------------------------------------
# Serving a latent-attention model: as in ``decode_step``, the stacked
# latent cache is carried through every group's layer scan, and each layer
# writes only its own rows into it (no per-group slice or concatenation of
# the cache)
# ---------------------------------------------------------------------------

#: tokens per pass of a prefill's feed-forward blocks: bounds the dense
#: layer's and the experts' intermediates (the sorted slots too) of a
#: long prefill; each token's result is its own, so chunks change nothing
PREFILL_CHUNK = 8192


def _latent_prefill(cfg: ModelConfig, params: dict, tokens, frontend_embeds,
                    max_len):
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    b, s = tokens.shape
    positions = jnp.arange(s)
    spec = cache_specs(cfg, b, max(max_len if max_len is not None
                                   else s + 64, s))
    cache = (jnp.zeros(spec["ckv"].shape, cfg.dtype),
             jnp.zeros(spec["kpe"].shape, cfg.dtype))

    def body(carry, layer_in):
        xc, (ckv, kpe) = carry
        lp, i = layer_in
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        o, c, k_pe = mla.attend(cfg, lp, h, positions)
        ckv = jax.lax.dynamic_update_slice(ckv, c[None], (i, 0, 0, 0))
        kpe = jax.lax.dynamic_update_slice(kpe, k_pe.swapaxes(1, 2)[None],
                                           (i, 0, 0, 0))
        xc = cm.map_token_chunks(
            lambda t: _ffn_block(cfg, lp, t[None])[0][0],
            (xc + o).reshape(b * s, -1), PREFILL_CHUNK)
        return (xc.reshape(b, s, -1), (ckv, kpe)), None

    x, (ckv, kpe) = _scan_groups(cfg, params, body, (x, cache))
    x = cm.rms_norm(x[:, -1, :], params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x, params["lm_head"]).astype(jnp.float32)
    return logits, {"ckv": ckv, "kpe": kpe, "len": jnp.int32(s)}


def _latent_decode_step(cfg: ModelConfig, params: dict, token, cache):
    x = jnp.take(params["embed"], token[:, None], axis=0)  # (B,1,D)
    pos = cache["len"]

    def body(carry, layer_in):
        xc, (ckv, kpe) = carry
        lp, i = layer_in
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        o, ckv, kpe = mla.decode(cfg, lp, h, ckv, kpe, i, pos)
        xc, _ = _ffn_block(cfg, lp, xc + o)
        return (xc, (ckv, kpe)), None

    x, (ckv, kpe) = _scan_groups(cfg, params, body,
                                 (x, (cache["ckv"], cache["kpe"])))
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x[:, 0, :], params["lm_head"]).astype(jnp.float32)
    return logits, {"ckv": ckv, "kpe": kpe, "len": pos + 1}


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Shapes of ``prefill``'s cache.  Dense K/V: ``(L, B, Hkv, T, hd)``,
    ``T`` = ``cache_rows(max_len)``; latent: ``(L, B, T, rank)`` rows and
    ``(L, B, rope, T)`` rope keys, ``T`` the kernel's block multiple
    (``mla.cache_len``)."""
    if cfg.kv_lora_rank:
        t = mla.cache_len(max_len)
        lb = (cfg.n_layers, batch)
        return {
            "ckv": jax.ShapeDtypeStruct(lb + (t, cfg.kv_lora_rank),
                                        cfg.dtype),
            "kpe": jax.ShapeDtypeStruct(lb + (cfg.qk_rope_head_dim, t),
                                        cfg.dtype),
            "len": jax.ShapeDtypeStruct((), jnp.int32),
        }
    shp = (cfg.n_layers, batch, cfg.n_kv_heads, cache_rows(max_len), cfg.hd)
    return {
        "k": jax.ShapeDtypeStruct(shp, cfg.dtype),
        "v": jax.ShapeDtypeStruct(shp, cfg.dtype),
        "len": jax.ShapeDtypeStruct((), jnp.int32),
    }


def cache_axes(cfg: ModelConfig) -> dict:
    if cfg.kv_lora_rank:
        return {"ckv": ("layer", "batch", "kv_seq", None),
                "kpe": ("layer", "batch", None, "kv_seq"), "len": ()}
    # the sequence takes the model axis (sequence-parallel decode); a
    # "kv" name on the heads, which come first, would take it instead
    ax = ("layer", "batch", None, "kv_seq", None)
    return {"k": ax, "v": ax, "len": ()}
