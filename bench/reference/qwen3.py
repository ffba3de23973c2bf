"""Plain float32 reference of the Qwen3 dense decoder.

Written from the published description (Qwen3 technical report,
arXiv:2505.09388, and the ``Qwen3ForCausalLM`` config), with no code of
the program under test.  Per layer:

    h = x + o_proj(attn(rope(q_norm(q_proj(n1(x)))), rope(k_norm(k_proj(n1(x)))), v_proj(n1(x))))
    x = h + down(silu(gate(n2(h))) * up(n2(h)))

with RMSNorm (eps ``rms_norm_eps``, over the last axis), q/k RMSNorm per
head over ``head_dim``, rotate-half RoPE with inverse frequencies
``theta ** (-2i / head_dim)``, causal softmax attention scaled by
``head_dim ** -0.5`` with each key/value head shared by
``heads / kv_heads`` query heads, then a final RMSNorm and the output
projection.  Everything is computed in float32 at
``jax.default_matmul_precision("highest")``, one layer at a time, so the
whole model never has to be held in float32.

Weights are the benchmark's own (``bench/drivers/live_serve.py`` makes
them from the seed), in this layout, layers stacked on axis 0:
``embed (V, D)``, ``attn_norm (L, D)``, ``wq (L, D, H, hd)``,
``wk``/``wv (L, D, Hkv, hd)``, ``wo (L, H, hd, D)``, ``q_norm``/
``k_norm (L, hd)``, ``mlp_norm (L, D)``, ``w_gate``/``w_up (L, D, F)``,
``w_down (L, F, D)``, ``final_norm (D,)``, and ``lm_head (D, V)``
unless ``tie_word_embeddings`` is set, when the output projection is
the embedding's transpose, as the published model ties them.  Norm
gains are stored as offsets from 1 (gain = 1 + offset), which is how
the served model holds them; published checkpoints hold the gain.

``quant="fp8"`` is the control: every linear layer's weight (per output
channel) and input (per token) is rounded to float8 e4m3 with an absmax
scale before the float32 product, the step below bfloat16 that a
faster serving path would take.  Norms, RoPE and softmax stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "mlp_norm", "w_gate", "w_up", "w_down")
F8_MAX = 448.0      # largest finite float8 e4m3 value


def _qdq(x, axes):
    """Round ``x`` to float8 e4m3 with an absmax scale over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, quant, w_in_axes):
    """``x (..., in) @ w`` with ``w``'s input axes ``w_in_axes``."""
    if quant == "fp8":
        x = _qdq(x, (-1,))
        w = _qdq(w, w_in_axes)
    return x, w


def _rms(x, gain_offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain_offset.astype(jnp.float32))


def rope_tables(seq: int, head_dim: int, theta: float):
    """cos/sin (seq, head_dim / 2), angles taken in float64."""
    inv = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64)
                    / head_dim)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """x (B, S, heads, hd); rotate-half form."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _layer(lw, x, cos, sin, *, eps, quant):
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    b, s, _ = x.shape
    h = _rms(x, lw["attn_norm"], eps)
    hq, wq = _linear(h, lw["wq"], quant, (0,))
    hk, wk = _linear(h, lw["wk"], quant, (0,))
    hv, wv = _linear(h, lw["wv"], quant, (0,))
    q = jnp.einsum("bsd,dhk->bshk", hq, wq)
    k = jnp.einsum("bsd,dhk->bshk", hk, wk)
    v = jnp.einsum("bsd,dhk->bshk", hv, wv)
    q = _rope(_rms(q, lw["q_norm"], eps), cos, sin)
    k = _rope(_rms(k, lw["k_norm"], eps), cos, sin)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    ha, wo = _linear(att.reshape(b, s, -1),
                     lw["wo"].reshape(-1, lw["wo"].shape[-1]), quant, (0,))
    x = x + ha @ wo
    h = _rms(x, lw["mlp_norm"], eps)
    hg, wg = _linear(h, lw["w_gate"], quant, (0,))
    hu, wu = _linear(h, lw["w_up"], quant, (0,))
    act = jax.nn.silu(hg @ wg) * (hu @ wu)
    hd_, wd = _linear(act, lw["w_down"], quant, (0,))
    return x + hd_ @ wd


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head_block, x, *, eps, quant):
    h = _rms(x, final_norm, eps)
    h, w = _linear(h, lm_head_block.astype(jnp.float32), quant, (0,))
    return h @ w


def logits(cfg: dict, w: dict, tokens, first: int, *, quant: str = "f32",
           vocab_block: int = 32768):
    """float32 logits (B, S - first, V) at positions ``first..S-1`` of
    ``tokens (B, S)``.  ``cfg`` holds the published keys."""
    eps = float(cfg["rms_norm_eps"])
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        cos, sin = rope_tables(tokens.shape[1], cfg["head_dim"],
                               float(cfg["rope_theta"]))
        for i in range(cfg["num_hidden_layers"]):
            lw = {k: w[k][i] for k in LAYER_KEYS}
            x = _layer(lw, x, cos, sin, eps=eps, quant=quant)
        x = x[:, first:]
        v = cfg["vocab_size"]
        if cfg["tie_word_embeddings"]:
            def head(j):
                return w["embed"][j:j + vocab_block].T
        else:
            def head(j):
                return w["lm_head"][:, j:j + vocab_block]
        out = [_head(w["final_norm"], head(j), x, eps=eps, quant=quant)
               for j in range(0, v, vocab_block)]
        return jnp.concatenate(out, axis=-1)
