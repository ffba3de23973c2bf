"""The work a call needs, counted from its shapes.

These counts are the yardstick for roofline and utilization shares: a
later change to a kernel or a step is read against the same work, so
work it stops doing counts as time saved and work it adds beyond this
count does not count at all.  ``least_s`` turns a count into the least
time the chip could take: the larger of operations over the peak rate
and bytes over the HBM bandwidth (``bench/peaks.json``).
"""
from __future__ import annotations

from typing import Dict, Tuple


def least_s(flops: float, bytes_: float, peaks: Dict[str, float]
            ) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


# -- simulator kernels -------------------------------------------------------


def minskew_round(n_tasks: int, n_scopes: int) -> Tuple[float, float]:
    """(ops, bytes) of one eligibility round: every task's membership in
    every scope is read once (int8) and compared twice, once for the
    scope minima and once against each minimum plus its skew; the task
    clocks (int32), run flags (int8), skews (int32) are read and one
    eligibility flag (int8) per task written."""
    ops = 2.0 * n_tasks * n_scopes
    bytes_ = n_tasks * n_scopes + 4.0 * n_tasks + n_tasks \
        + 4.0 * n_scopes + n_tasks
    return ops, bytes_


def hub_route_pass(n_msgs: int) -> Tuple[float, float]:
    """(ops, bytes) of one fan-out pass over sorted messages: per message
    a send time, a serialization time, a link id and a latency (int32
    each) are read, a max and two adds computed, a visibility written."""
    return 3.0 * n_msgs, 20.0 * n_msgs


# -- dense decoder (Qwen3-style) ---------------------------------------------


def decoder_params(cfg: Dict) -> Dict[str, int]:
    """Parameter counts from the published keys of a dense decoder.
    ``head`` is the output projection's size, whose product every token
    needs; with tied embeddings it is the embedding itself, and
    ``published`` counts it once."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n_layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * f
    norms = 2 * d + 2 * hd
    embed, head = vocab * d, d * vocab
    published = n_layers * (attn + mlp + norms) + embed + d \
        + (0 if cfg["tie_word_embeddings"] else head)
    return {"layer_matmul": attn + mlp, "layer_norms": norms,
            "layers": n_layers, "embed": embed, "head": head,
            "final_norm": d, "published": published}


def decode_step(cfg: Dict, batch: int, context: int, bytes_per_param=2,
                bytes_per_kv=2) -> Tuple[float, float]:
    """(flops, bytes) of one decode step for ``batch`` sequences that
    each attend over ``context`` positions (the new one included).

    flops: 2 per weight per token in every matmul, the output head
    included, plus attention's two products, 4 * heads * head_dim per
    attended position per layer.  bytes: every matmul and norm weight
    read once (embedding rows only for the batch's tokens), the cache's
    keys and values of the attended positions read once, the new
    position's written once."""
    p = decoder_params(cfg)
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n_layers = p["layers"]
    flops = 2.0 * batch * (n_layers * p["layer_matmul"] + p["head"]) \
        + 4.0 * batch * n_layers * h * hd * context
    weights = n_layers * (p["layer_matmul"] + p["layer_norms"]) \
        + p["head"] + p["final_norm"]
    kv_bytes = 2.0 * n_layers * batch * kv * hd * bytes_per_kv
    bytes_ = weights * bytes_per_param \
        + batch * cfg["hidden_size"] * bytes_per_param \
        + kv_bytes * (context + 1)
    return flops, bytes_
