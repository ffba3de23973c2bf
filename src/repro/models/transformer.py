"""Decoder-only transformer family: dense, expert (MoE) and vision
backbones (phi3-medium-14b, glm4-9b, deepseek-coder-33b, qwen3-4b,
pixtral-12b with its patch-embedding stub, olmoe-1b-7b,
moonshot-v1-16b-a3b).

Layer: pre-RMSNorm -> attention -> residual -> pre-RMSNorm -> SwiGLU MLP
or experts -> residual.

The attention is one of two modules with one interface, chosen once per
model by ``_attention``: grouped-query (``models/gqa.py``) or latent
(``models/mla.py``, when ``kv_lora_rank`` is set).  Each owns its
weights (``params``/``specs``/``axes``), its full-sequence ``attend``,
which also returns the rows a layer leaves in the cache, its one-token
``decode``, which writes the layer's new row into the stacked cache and
attends it in place, and the cache's format (``write``,
``cache_specs``, ``cache_axes``).  The decoder below knows none of it:
it carries the module's stacked cache through the layer scan.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import gqa, mla
from repro.models.common import ModelConfig
from repro.parallel import ctx as pctx

#: tokens per device in one pass of a prefill's feed-forward blocks:
#: bounds the dense layer's and the experts' intermediates (the sorted
#: slots too) of a long prefill; each token's result is its own, so
#: passes change nothing
PREFILL_CHUNK = 8192

# ---------------------------------------------------------------------------
# Per-layer params
# ---------------------------------------------------------------------------


def _attention(cfg: ModelConfig):
    """The attention module of every layer of the model."""
    return mla if cfg.kv_lora_rank else gqa


def _layer_init(cfg: ModelConfig, moe_ffn: bool):
    d, dt = cfg.d_model, cfg.dtype
    att = _attention(cfg)

    def init_one(key):
        ks = jax.random.split(key, 8)
        p = {"ln1": jnp.zeros((d,), dt), "ln2": jnp.zeros((d,), dt),
             **att.params(ks[:4], cfg)}
        if moe_ffn:
            from repro.models import moe

            p["moe"] = moe.moe_params(ks[4], cfg)
        else:
            p["mlp"] = cm.mlp_params(ks[4], d, cfg.d_ff, dt)
        return p

    return init_one


def _layer_specs(cfg: ModelConfig, moe_ffn: bool) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    p = {"ln1": jax.ShapeDtypeStruct((d,), dt),
         "ln2": jax.ShapeDtypeStruct((d,), dt), **_attention(cfg).specs(cfg)}
    if moe_ffn:
        from repro.models import moe

        p["moe"] = moe.moe_specs(cfg)
    else:
        p["mlp"] = cm.mlp_specs(d, cfg.d_ff, dt)
    return p


def _layer_axes(cfg: ModelConfig, moe_ffn: bool) -> dict:
    p = {"ln1": (None,), "ln2": (None,), **_attention(cfg).axes(cfg)}
    if moe_ffn:
        from repro.models import moe

        p["moe"] = moe.moe_axes(cfg)
    else:
        p["mlp"] = dict(cm.MLP_AXES)
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
    att = _attention(cfg)
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    positions = jnp.arange(tokens.shape[1])

    def body(carry, lp):
        xc, aux = carry
        o, _ = att.attend(cfg, lp, cm.rms_norm(xc, lp["ln1"], cfg.norm_eps),
                          positions)
        xc, a = _ffn_block(cfg, lp, xc + o)
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
    """Returns (last-position logits (B,V), cache).

    The cache is the attention module's stacked rows (``cache_specs``)
    and ``"len"``, the positions filled.  ``max_len`` (default S + 64)
    reserves decode headroom.  Every layer writes its rows, zero-padded
    to the cache's positions, into the stacked cache carried through the
    layer scan: the write covers the layer whole, so on a mesh the shards
    of the rows line up with those of a sequence-sharded cache.
    """
    att = _attention(cfg)
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    b, s = tokens.shape
    positions = jnp.arange(s)
    spec = att.cache_specs(cfg, b, max(max_len if max_len is not None
                                       else s + 64, s))
    cache = {n: jnp.zeros(a.shape, a.dtype) for n, a in spec.items()}

    def body(carry, layer_in):
        xc, rows = carry
        lp, i = layer_in
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        o, new = att.attend(cfg, lp, h, positions)
        xc = _prefill_ffn(cfg, lp, xc + o)
        new = {n: jnp.pad(r, [(0, t - a) for a, t in
                              zip(r.shape, rows[n].shape[1:])])
               for n, r in new.items()}
        return (xc, att.write(rows, new, i, 0)), None

    x, cache = _scan_groups(cfg, params, body, (x, cache))
    x = cm.rms_norm(x[:, -1, :], params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x, params["lm_head"]).astype(jnp.float32)
    return logits, dict(cache, len=jnp.int32(s))


def _prefill_ffn(cfg: ModelConfig, lp: dict, x: jnp.ndarray) -> jnp.ndarray:
    """The feed-forward block over a prefill's (B, S, D) tokens, in
    passes over equal spans of the sequence of at most
    ``PREFILL_CHUNK`` tokens per device of the active mesh each (zero
    positions pad the last span and are dropped).  Every pass keeps the
    whole batch, so a batch sharded over the mesh stays as it is; each
    pass overwrites its span in place."""
    b, s, _ = x.shape
    mesh = pctx.get_mesh()
    per_pass = PREFILL_CHUNK * (mesh.size if mesh is not None else 1)
    n = -(-s // max(per_pass // b, 1))
    if n == 1:          # one pass: the block as it compiles on its own
        return _ffn_block(cfg, lp, x)[0]
    c = -(-s // n)
    x = jnp.pad(x, ((0, 0), (0, n * c - s), (0, 0)))

    def one(j, y):
        t = jax.lax.dynamic_slice_in_dim(y, j * c, c, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            y, _ffn_block(cfg, lp, t)[0], j * c, axis=1)

    return jax.lax.fori_loop(0, n, one, x)[:, :s]


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

    The stacked cache is carried through the layer scan: each layer
    writes only its new row, at ``(layer, len)``, and attends to its own
    layer of the carry, so a caller that donates the cache has it
    updated in place.  Returns (logits (B,V), updated cache)."""
    att = _attention(cfg)
    x = jnp.take(params["embed"], token[:, None], axis=0)  # (B,1,D)
    pos = cache["len"]

    def body(carry, layer_in):
        xc, rows = carry
        lp, i = layer_in
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        o, rows = att.decode(cfg, lp, h, rows, i, pos)
        xc, _ = _ffn_block(cfg, lp, xc + o)
        return (xc, rows), None

    rows = {n: a for n, a in cache.items() if n != "len"}
    x, rows = _scan_groups(cfg, params, body, (x, rows))
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x[:, 0, :], params["lm_head"]).astype(jnp.float32)
    return logits, dict(rows, len=pos + 1)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Shapes of ``prefill``'s cache: the attention module's stacked
    rows for ``max_len`` positions, and ``"len"``."""
    return dict(_attention(cfg).cache_specs(cfg, batch, max_len),
                len=jax.ShapeDtypeStruct((), jnp.int32))


def cache_axes(cfg: ModelConfig) -> dict:
    return dict(_attention(cfg).cache_axes(cfg), len=())
