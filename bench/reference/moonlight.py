"""Plain float32 reference of Moonlight-16B-A3B (DeepSeek-V3 architecture).

Written from the published description (the DeepSeek-V3 modelling code,
``DeepseekV3ForCausalLM``, and the Moonlight ``config.json``), with no
code of the program under test.  Per layer, with RMSNorm over the last
axis (eps ``rms_norm_eps``)::

    h = n1(x)
    [q_nope ‖ q_pe] = h @ q_proj                       per head, nope 128 + rope 64
    [c_kv ‖ k_pe]   = h @ kv_a_proj_with_mqa
    [k_nope ‖ v]    = kv_a_layernorm(c_kv) @ kv_b_proj  per head, 128 + 128
    q_pe, k_pe      = rope(q_pe), rope(k_pe)           k_pe shared by the heads
    x = x + o_proj(softmax([q_nope ‖ q_pe] . [k_nope ‖ k_pe] * 192 ** -0.5, causal) v)
    h = n2(x)
    x = x + mlp(h)                       layers < first_k_dense_replace
    x = x + routed(h) + shared(h)        the others

RoPE as the modelling code applies it: the rope features are
de-interleaved (even ones first, then odd ones), then rotated halves
with ``cos``/``sin`` of ``[f, f]``, ``f = position * theta ** (-2i / 64)``.
Attention is computed in full, keys and values decompressed, never in
latent space.  Routing (``scoring_func`` sigmoid, ``topk_method``
noaux_tc with one group): scores ``sigmoid(h @ gate)`` in float32; the
``num_experts_per_tok`` experts of highest ``score + e_score_correction_bias``
are chosen; their weights are their unbiased scores over the sum of the
chosen scores (plus 1e-20), times ``routed_scaling_factor``.  Each
expert and the shared experts (one SwiGLU of ``n_shared_experts *
moe_intermediate_size``) is ``down(silu(gate(h)) * up(h))``.

Computed in float32 at ``jax.default_matmul_precision("highest")``, one
layer at a time, with every held expert run densely over every token
and weighted by its routing weight (0 where the token did not choose
it).

``logits(..., margins=True)`` also gives, per position and expert
layer, how near the routing came to choosing otherwise for a held
expert (``routing_margin``): where that is small, a server that rounds
the router's input differently may rightly compute another set of held
experts.

Departures from the published model, all shared with the program:

* random weights from the benchmark's seed, the correction bias too;
* this chip's share of the expert-parallel deployment: the router
  routes over all ``deployment.n_routed_experts`` experts, but only the
  ``n_routed_experts`` held ones (``deployment.first_held_expert`` on)
  compute, and their partial sum goes on to the next layer as the
  layer's routed output;
* no end-of-sequence stop;
* norm gains stored as offsets from 1 (gain = 1 + offset).

Weights, in the benchmark's layout (``drivers/live_serve_mla.py``):
``embed (V, D)``, ``lm_head (D, V)``, ``final_norm (D,)``, and per group
of stacked layers, ``dense`` (the first ``first_k_dense_replace``) and
``moe`` (the rest): ``attn_norm``/``mlp_norm (n, D)``, ``wq (n, D, H,
192)``, ``wkv_a (n, D, 576)``, ``kv_norm (n, 512)``, ``wkv_b (n, 512,
H, 256)``, ``wo (n, H, 128, D)``; ``dense`` also ``w_gate``/``w_up (n, D,
F)``, ``w_down (n, F, D)``; ``moe`` also ``router (n, D, E)`` and
``router_bias (n, E)`` (float32), the held experts' ``e_gate``/``e_up
(n, Eh, D, Fe)``, ``e_down (n, Eh, Fe, D)`` and the shared experts'
``s_gate``/``s_up (n, D, Fs)``, ``s_down (n, Fs, D)``.

``quant="fp8"`` is the control: every linear layer's weight (per output
channel) and input (per token) is rounded to float8 e4m3 with an absmax
scale before the float32 product, the step below bfloat16 that a
faster serving path would take.  The router, norms, RoPE and softmax
stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0      # largest finite float8 e4m3 value
ATTN_KEYS = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
             "mlp_norm")
DENSE_KEYS = ATTN_KEYS + ("w_gate", "w_up", "w_down")
MOE_KEYS = ATTN_KEYS + ("router", "router_bias", "e_gate", "e_up", "e_down",
                        "s_gate", "s_up", "s_down")


def _qdq(x, axes):
    """Round ``x`` to float8 e4m3 with an absmax scale over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant, spec, w_in_axes):
    """``einsum(spec, x, w)``; under fp8 both rounded first, ``x`` per
    token over the axes it contracts, ``w`` per output over
    ``w_in_axes``."""
    if quant == "fp8":
        x = _qdq(x, tuple(range(-len(w_in_axes), 0)))
        w = _qdq(w, w_in_axes)
    return jnp.einsum(spec, x, w)


def _rms(x, gain_offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain_offset)


def rope_tables(seq: int, dim: int, theta: float):
    """cos/sin (seq, dim) of ``[f, f]``, angles taken in float64."""
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def _rope(x, cos, sin):
    """x (B, S, n, P), the modelling code's pairing: de-interleave, then
    ``x * cos + rotate_half(x) * sin``."""
    b, s, n, p = x.shape
    x = x.reshape(b, s, n, p // 2, 2).swapaxes(-1, -2).reshape(b, s, n, p)
    rot = jnp.concatenate([-x[..., p // 2:], x[..., :p // 2]], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def _swiglu(h, wg, wu, wd, quant):
    g = _mm(h, wg, quant, "bsd,df->bsf", (0,))
    u = _mm(h, wu, quant, "bsd,df->bsf", (0,))
    return _mm(jax.nn.silu(g) * u, wd, quant, "bsf,fd->bsd", (0,))


def _attention(lw, x, cos, sin, *, eps, nope, rank, quant):
    b, s, _ = x.shape
    h = _rms(x, lw["attn_norm"], eps)
    q = _mm(h, lw["wq"], quant, "bsd,dhk->bshk", (0,))
    kv_a = _mm(h, lw["wkv_a"], quant, "bsd,dk->bsk", (0,))
    c = _rms(kv_a[..., :rank], lw["kv_norm"], eps)
    kv = _mm(c, lw["wkv_b"], quant, "bsr,rhk->bshk", (0,))
    heads = q.shape[2]
    q_pe = _rope(q[..., nope:], cos, sin)
    k_pe = _rope(kv_a[..., None, rank:], cos, sin)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads,
                                                 k_pe.shape[-1]))], axis=-1)
    v = kv[..., nope:]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return x + _mm(att, lw["wo"], quant, "bshv,hvd->bsd", (0, 1))


@functools.partial(jax.jit, static_argnames=("eps", "nope", "rank", "quant"))
def _dense_layer(lw, x, cos, sin, *, eps, nope, rank, quant):
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    x = _attention(lw, x, cos, sin, eps=eps, nope=nope, rank=rank,
                   quant=quant)
    h = _rms(x, lw["mlp_norm"], eps)
    return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], quant)


def routing(h, router, bias, *, top_k, scale, first, held):
    """Per token, the weight of each held expert (T.., Eh): the
    normalised, scaled score where the token chose it, else 0."""
    scores = jax.nn.sigmoid(jnp.einsum("...d,de->...e", h, router))
    _, ids = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    chose = ids[..., None] == first + jnp.arange(held)      # (.., k, Eh)
    return jnp.sum(jnp.where(chose, w[..., None], 0.0), axis=-2)


def routing_margin(h, router, bias, *, top_k, first, held):
    """Per token (T..,), the least distance of a held expert's biased
    score from the boundary between the chosen and the rest: for a
    chosen held expert its biased score less the best unchosen one, for
    an unchosen held expert the last chosen one less its own."""
    c = jax.nn.sigmoid(jnp.einsum("...d,de->...e", h, router)) + bias
    top = jax.lax.top_k(c, top_k + 1)[0]
    last, after = top[..., top_k - 1:top_k], top[..., top_k:]
    mine = c[..., first:first + held]
    return jnp.min(jnp.where(mine >= last, mine - after, last - mine),
                   axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "eps", "nope", "rank", "quant", "top_k", "scale", "first"))
def _moe_layer(lw, x, cos, sin, *, eps, nope, rank, quant, top_k, scale,
               first):
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    x = _attention(lw, x, cos, sin, eps=eps, nope=nope, rank=rank,
                   quant=quant)
    h = _rms(x, lw["mlp_norm"], eps)
    held = lw["e_gate"].shape[0]
    gate = routing(h, lw["router"], lw["router_bias"], top_k=top_k,
                   scale=scale, first=first, held=held)
    margin = routing_margin(h, lw["router"], lw["router_bias"], top_k=top_k,
                            first=first, held=held)
    g = _mm(h, lw["e_gate"], quant, "bsd,edf->bsef", (1,))
    u = _mm(h, lw["e_up"], quant, "bsd,edf->bsef", (1,))
    per_expert = _mm(jax.nn.silu(g) * u, lw["e_down"], quant,
                     "bsef,efd->bsed", (1,))
    routed = jnp.einsum("bse,bsed->bsd", gate, per_expert)
    shared = _swiglu(h, lw["s_gate"], lw["s_up"], lw["s_down"], quant)
    return x + routed + shared, margin


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(final_norm, lm_head_block, x, *, eps, quant):
    h = _rms(x, final_norm.astype(jnp.float32), eps)
    return _mm(h, lm_head_block.astype(jnp.float32), quant, "bsd,dv->bsv",
               (0,))


def logits(cfg: dict, w: dict, tokens, first: int, *, quant: str = "f32",
           vocab_block: int = 32768, margins: bool = False):
    """float32 logits (B, S - first, V) at positions ``first..S-1`` of
    ``tokens (B, S)``.  ``cfg`` holds the published keys and the
    ``deployment`` block.  With ``margins``, (logits, routing margins
    (B, S - first, expert layers))."""
    eps = float(cfg["rms_norm_eps"])
    dims = dict(eps=eps, nope=cfg["qk_nope_head_dim"],
                rank=cfg["kv_lora_rank"], quant=quant)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        cos, sin = rope_tables(tokens.shape[1], cfg["qk_rope_head_dim"],
                               float(cfg["rope_theta"]))
        k = cfg["first_k_dense_replace"]
        ms = []
        for i in range(cfg["num_hidden_layers"]):
            if i < k:
                lw = {n: w["dense"][n][i] for n in DENSE_KEYS}
                x = _dense_layer(lw, x, cos, sin, **dims)
            else:
                lw = {n: w["moe"][n][i - k] for n in MOE_KEYS}
                x, m = _moe_layer(
                    lw, x, cos, sin, **dims, top_k=cfg["num_experts_per_tok"],
                    scale=float(cfg["routed_scaling_factor"]),
                    first=cfg["deployment"]["first_held_expert"])
                ms.append(m[:, first:])
        x = x[:, first:]
        out = [_head(w["final_norm"], w["lm_head"][:, j:j + vocab_block], x,
                     eps=eps, quant=quant)
               for j in range(0, cfg["vocab_size"], vocab_block)]
        out = jnp.concatenate(out, axis=-1)
        return (out, jnp.stack(ms, axis=-1)) if margins else out
