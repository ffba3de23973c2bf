"""Common model-definition machinery shared by every architecture family.

Design notes
------------
* Parameters are plain nested dicts of ``jnp.ndarray`` — no flax/haiku. Every
  model exposes:
    - ``init(cfg, key)``            -> param pytree (materialized)
    - ``param_specs(cfg)``          -> pytree of ``jax.ShapeDtypeStruct`` (no alloc)
    - ``logical_axes(cfg)``         -> pytree of logical-axis tuples (for sharding)
    - ``forward(cfg, params, ...)`` -> logits
* Per-layer parameters are stacked with a leading ``L`` dimension so the layer
  stack lowers to a single ``lax.scan`` — small HLO, fast multi-device compile.
* Logical axis names (mapped to mesh axes in ``repro.parallel.sharding``):
    "embed"   – d_model dim            (FSDP candidate)
    "heads"   – attention head dim     (TP)
    "kv"      – kv-head dim            (TP when divisible)
    "mlp"     – feed-forward hidden    (TP)
    "vocab"   – vocabulary             (TP)
    "expert"  – MoE expert dim         (EP)
    "layer"   – stacked layer dim      (never sharded)
    None      – replicated
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | xlstm | rglru | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None   # default: d_model // n_heads
    rope_theta: float = 10_000.0
    qk_norm: bool = False
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # --- latent attention (MLA, DeepSeek-V2/V3): on when kv_lora_rank > 0;
    #     the cache holds one compressed KV row per position ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- MoE ---
    n_experts: int = 0               # the router's width
    top_k: int = 0
    capacity_factor: float = 1.25    # expert-parallel (mesh) path only
    router_aux_coef: float = 0.01
    moe_d_ff: int = 0                # routed expert width (0: d_ff)
    n_shared_experts: int = 0        # run on every token, as one SwiGLU
    #                                  of width n_shared_experts * moe_d_ff
    first_k_dense: int = 0           # leading layers with a dense SwiGLU
    #                                  of width d_ff instead of experts
    scoring_func: str = "softmax"    # "softmax" | "sigmoid" (experts
    #                                  chosen by score plus a learned
    #                                  correction bias, DeepSeek-V3)
    routed_scaling: float = 1.0      # scale of the normalised weights
    n_held_experts: int = 0          # experts held here (0: all); the
    first_held_expert: int = 0       # router still routes over n_experts
    # --- recurrentgemma / hybrid ---
    window: int = 0                  # sliding local-attention window (0 = full)
    lru_width: int = 0
    attn_every: int = 0              # 1 attention block per `attn_every` blocks
    # --- xlstm ---
    slstm_every: int = 0             # 1 sLSTM block per `slstm_every` blocks
    mlstm_proj_factor: float = 2.0
    slstm_ff_factor: float = 4.0 / 3.0
    # --- encoder-decoder ---
    n_enc_layers: int = 0            # if >0, family == encdec
    # --- multimodal frontend stubs ---
    frontend: str = ""               # "" | "patch" | "audio"
    frontend_dim: int = 0            # raw embedding dim provided by the stub
    n_frontend_tokens: int = 0       # tokens contributed by the frontend
    # --- numerics ---
    dtype: Any = jnp.bfloat16
    # --- training-time knobs (overridable per shape) ---
    remat: bool = True
    scan_layers: bool = True
    # --- beyond-paper optimization knobs (§Perf; default = baseline) ---
    tp_attention: bool = False   # TP-aligned GQA: repeat KV weights to one
    #                              kv head per q head + zero-pad heads to
    #                              the model-axis width, so the attention
    #                              einsums shard instead of replicating
    #                              (numerically identical; see EXPERIMENTS)
    sp_decode: bool = False      # pin decode attention to the sequence-
    #                              sharded KV layout (flash-decoding style)
    #                              instead of letting GSPMD reshard the
    #                              cache to kv-head sharding per layer
    #                              ("involuntary full rematerialization")
    gather_weights_once: bool = False  # hoist the FSDP all-gather out of
    #                              the microbatch/remat passes: gather bf16
    #                              weights to TP-only layout once per step
    #                              (ZeRO-1-for-compute; needs params*2/TP
    #                              bytes of HBM), reduce-scatter grads back
    remat_policy: str = "nothing"  # "nothing" | "dots" — remat checkpoint
    #                              policy (dots saves matmul outputs:
    #                              less recompute, more activation HBM)
    causal_slice: bool = False   # triangle-sliced chunked attention in the
    #                              unrolled path (flash-kernel block-skip
    #                              analogue; ~2x attention flops saving)

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self) -> int:
        return self.n_held_experts or self.n_experts

    def n_params(self) -> int:
        """Parameter count, derived from the real param specs (no alloc)."""
        from repro.models import registry

        specs = registry.param_specs(self)
        return int(sum(math.prod(s.shape) for s in jax.tree.leaves(specs)))


# ---------------------------------------------------------------------------
# Initializers (shape-only friendly)
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis: int = 0) -> jnp.ndarray:
    fan_in = shape[in_axis] if isinstance(in_axis, int) else math.prod(
        shape[a] for a in in_axis)
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key, shape, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def head_rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    """QK-norm: RMS over the head_dim of a (..., H, hd) tensor."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(hd: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                      # (hd/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def mlp_params(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype, in_axis=0),
    }


def mlp_specs(d_model: int, d_ff: int, dtype) -> dict:
    return {
        "w_gate": jax.ShapeDtypeStruct((d_model, d_ff), dtype),
        "w_up": jax.ShapeDtypeStruct((d_model, d_ff), dtype),
        "w_down": jax.ShapeDtypeStruct((d_ff, d_model), dtype),
    }


MLP_AXES = {
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
}


def mlp_forward(p: dict, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.dot(x, p["w_gate"])
    u = jnp.dot(x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.dot(h, p["w_down"])


def map_token_chunks(fn: Callable, rows, chunk: int):
    """``fn(rows)`` over row chunks of at most ``chunk`` rows.

    ``rows`` is a pytree of arrays that share their leading (token) axis;
    ``fn`` maps such a pytree to one array with that leading axis.  Above
    ``chunk`` rows the tokens run through ``lax.map`` in equal chunks
    (zero rows pad the last one and are dropped), so the intermediates of
    a wide layer hold ``chunk`` rows at a time, not every token's."""
    t = jax.tree.leaves(rows)[0].shape[0]
    if t <= chunk:
        return fn(rows)
    n = -(-t // chunk)
    c = -(-t // n)

    def split(a):
        a = jnp.pad(a, ((0, n * c - t),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(n, c, *a.shape[1:])
    out = jax.lax.map(fn, jax.tree.map(split, rows))
    return out.reshape(n * c, *out.shape[2:])[:t]


# ---------------------------------------------------------------------------
# Stacking helpers (scan over layers)
# ---------------------------------------------------------------------------


def stack_layer_params(init_one: Callable[[jax.Array], dict], key,
                       n_layers: int) -> dict:
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_one)(keys)


def stacked_specs(spec_one: dict, n_layers: int) -> dict:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_layers,) + s.shape, s.dtype), spec_one)


def stacked_axes(axes_one: dict) -> dict:
    return jax.tree.map(lambda a: ("layer",) + a, axes_one,
                        is_leaf=lambda x: isinstance(x, tuple))


def maybe_remat(fn: Callable, cfg: ModelConfig) -> Callable:
    if not cfg.remat:
        return fn
    policy = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }[cfg.remat_policy]
    return jax.checkpoint(fn, policy=policy)


def scan_or_unroll(body: Callable, carry, xs, use_scan: bool):
    """``lax.scan`` when use_scan, else a python loop (counting mode:
    XLA cost_analysis counts while bodies once, so the dry-run counting
    pass unrolls).  body(carry, x) -> (carry, y)."""
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def scan_layers(body: Callable, x, layer_params, cfg: ModelConfig,
                extra_carry=None):
    """Run ``body(carry, one_layer_params) -> carry`` over stacked params."""
    fn = maybe_remat(body, cfg)
    if cfg.scan_layers:
        carry, _ = jax.lax.scan(lambda c, p: (fn(c, p), None), x, layer_params)
        return carry
    n = jax.tree.leaves(layer_params)[0].shape[0]
    for i in range(n):
        p_i = jax.tree.map(lambda a: a[i], layer_params)
        x = fn(x, p_i)
    return x


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                          z_loss: float = 1e-4) -> jnp.ndarray:
    """logits (..., V) fp-any; labels (...) int32. Returns mean loss (fp32)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - gold
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return jnp.mean(loss)
