"""The assigned input-shape grid and per-(arch x shape) applicability.

LM transformer shapes are seq_len x global_batch.  ``decode_*``/``long_*``
lower ``serve_step`` (one new token against a KV/recurrent cache of
seq_len), NOT ``train_step``.  ``long_500k`` requires sub-quadratic
attention: it runs for the SSM/hybrid archs (xlstm, recurrentgemma) and is
skipped (recorded N/A) for pure full-attention archs — see DESIGN.md
§Arch-applicability.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import registry
from repro.models.common import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str        # train | prefill | decode
    seq: int
    batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

# Gradient-accumulation microbatch count per arch for train_4k
# (chosen so per-layer saved activations fit HBM; see DESIGN.md §6).
MICROBATCH: Dict[str, int] = {
    "phi3_medium_14b": 4,
    "glm4_9b": 4,
    "deepseek_coder_33b": 8,
    "qwen3_4b": 2,
    "seamless_m4t_medium": 1,
    "xlstm_1_3b": 2,
    # 27 latent-attention layers: per layer about 4/3 of the saved
    # attention activations of the 48-layer multi-head stack it was sized
    # for, in all about 3/4 of them (an estimate), so 2 is kept
    "moonshot_v1_16b_a3b": 2,
    "olmoe_1b_7b": 1,
    "pixtral_12b": 4,
    "recurrentgemma_9b": 4,
}


def applicable(cfg: ModelConfig, shape: ShapeSpec) -> Optional[str]:
    """None if the (arch, shape) cell runs; else a skip reason (N/A cell)."""
    if shape.name == "long_500k" and not registry.sub_quadratic(cfg):
        return ("full-attention arch: 512k dense-KV decode is not "
                "sub-quadratic; skipped per assignment")
    return None


def frontend_tokens(cfg: ModelConfig, seq: int) -> int:
    if cfg.frontend == "patch":
        return min(cfg.n_frontend_tokens, seq // 2)
    if cfg.frontend == "audio":
        from repro.models import encdec

        return encdec.enc_len(cfg, seq)
    return 0


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train  -> {tokens, labels[, frontend_embeds]}
    prefill-> {tokens[, frontend_embeds]}
    decode -> {token, cache}
    """
    b, s = shape.batch, shape.seq
    i32 = jnp.int32
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), i32),
            "labels": jax.ShapeDtypeStruct((b, s), i32),
        }
        nf = frontend_tokens(cfg, s)
        if nf:
            specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                (b, nf, cfg.frontend_dim), jnp.float32)
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        nf = frontend_tokens(cfg, s)
        if nf:
            specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                (b, nf, cfg.frontend_dim), jnp.float32)
        return specs
    if shape.kind == "decode":
        return {
            "token": jax.ShapeDtypeStruct((b,), i32),
            "cache": registry.cache_specs(cfg, b, s),
        }
    raise ValueError(shape.kind)
