"""The work a decode step of a latent-attention expert model needs
(DeepSeek-V3 keys: Moonlight), counted from the published keys and the
experts this chip holds.

As in ``workcount``, these counts are the yardstick of the roofline and
utilization shares: work the program stops doing counts as time saved,
work it adds beyond this count does not count.  Weights are bfloat16,
the router and its correction bias float32.  ``n_routed_experts`` is the
experts held; ``deployment.n_routed_experts`` the router's width.
"""
from __future__ import annotations

from typing import Dict, Tuple

BF16, F32 = 2, 4


def params(cfg: Dict) -> Dict[str, int]:
    """Parameter counts: per layer of each kind, and in all for the
    published model (``published``, every routed expert) and for what
    this chip holds (``held``)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    e_pub = cfg["deployment"]["n_routed_experts"]
    fe = cfg["moe_intermediate_size"]
    k, n_layers = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    attn = d * h * (nope + rope) + d * (r + rope) + r * h * (nope + v) \
        + h * v * d
    norms = 2 * d + r
    dense = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * fe
    router = d * e_pub + e_pub
    shared = 3 * d * cfg["n_shared_experts"] * fe
    embed = head = cfg["vocab_size"] * d

    def total(held):
        return n_layers * (attn + norms) + k * dense \
            + (n_layers - k) * (held * expert + router + shared) \
            + embed + head + d
    return {"attn": attn, "norms": norms, "dense": dense, "expert": expert,
            "router": router, "shared": shared, "embed": embed,
            "head": head, "final_norm": d, "layers": n_layers,
            "dense_layers": k, "moe_layers": n_layers - k,
            "published": total(e_pub),
            "held": total(cfg["n_routed_experts"])}


def latent_row_bytes(cfg: Dict) -> int:
    """Cache bytes of one position of one layer: ``c_kv`` and ``k_pe``."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16


def mla_attention(cfg: Dict, batch: int, context: int
                  ) -> Tuple[float, float]:
    """(flops, bytes) of the latent decode attention of one step, every
    layer (``kernels/mla_decode.py``): each head's scores against the
    ``context`` attended latent rows (R + P wide) and their weighted sum
    of the rows' ``c_kv`` (R wide); the rows read once, the absorbed
    queries read and the latent outputs written."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    p = cfg["qk_rope_head_dim"]
    n = cfg["num_hidden_layers"]
    flops = 2.0 * n * batch * h * (2 * r + p) * context
    bytes_ = n * batch * (context * latent_row_bytes(cfg)
                          + h * (r + p) * BF16 + h * r * BF16)
    return flops, bytes_


def experts(cfg: Dict, batch: int) -> Tuple[float, float]:
    """(flops, bytes) of one step's held and shared experts, every expert
    layer: their weights read once; each token's expected share of its
    ``num_experts_per_tok`` routed slots on held experts (routing spread
    evenly) plus every token through the shared experts."""
    p = params(cfg)
    held = cfg["n_routed_experts"]
    frac = held / cfg["deployment"]["n_routed_experts"]
    per_layer = p["expert"] * cfg["num_experts_per_tok"] * frac \
        + p["shared"]
    flops = 2.0 * batch * p["moe_layers"] * per_layer
    bytes_ = p["moe_layers"] * (held * p["expert"] + p["shared"]) * BF16
    return flops, bytes_


def decode_step(cfg: Dict, batch: int, context: int) -> Tuple[float, float]:
    """(flops, bytes) of one decode step for ``batch`` sequences that each
    attend over ``context`` positions (the new one included).

    flops: 2 per weight per token in every matmul a token uses (the
    latent attention's projections, ``kv_b`` once whether absorbed or
    not; the dense layer; router, shared experts and its expected share
    of the held experts; the output head), plus the latent attention's
    products.  bytes: every weight held read once (embedding rows only
    for the batch's tokens), the attended latent rows read once, the new
    position's row written once."""
    p = params(cfg)
    held = cfg["n_routed_experts"]
    frac = held / cfg["deployment"]["n_routed_experts"]
    n, k, m = p["layers"], p["dense_layers"], p["moe_layers"]
    per_token = n * p["attn"] + k * p["dense"] \
        + m * (p["router"] + p["shared"]
               + p["expert"] * cfg["num_experts_per_tok"] * frac) \
        + p["head"]
    att_flops, _ = mla_attention(cfg, batch, context)
    flops = 2.0 * batch * per_token + att_flops
    router_f32 = m * p["router"]
    weights = (p["held"] - p["embed"] - router_f32) * BF16 \
        + router_f32 * F32
    cache = n * batch * latent_row_bytes(cfg) * (context + 1)
    bytes_ = weights + batch * cfg["hidden_size"] * BF16 + cache
    return flops, bytes_
