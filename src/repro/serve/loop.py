"""Batched serving loop: prefill + decode with per-request bookkeeping.

Single static batch per wave (continuous batching is a scheduling-layer
concern that LiveStack simulates; the execution layer here provides the
real prefill/decode steps with KV-cache reuse, EOS early-exit, and
latency accounting per request).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.models.common import ModelConfig


@dataclasses.dataclass
class ServeStats:
    prefill_s: float
    decode_s: float
    tokens_out: int
    per_token_ms: float
    throughput_tok_s: float
    decode_steps: int = 0


class BatchServer:
    """Prefill and greedy decode of one static batch.

    ``_decode(params, token, cache)`` consumes its cache: the argument is
    donated, so the step updates the cache in place (dense K/V stored
    ``(L, B, Hkv, T, hd)``, latent rows as ``prefill`` lays them out) and
    the cache passed in is deleted.  Rebind it to the step's output."""

    def __init__(self, cfg: ModelConfig, params, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None, pad_id: int = 0):
        self.cfg = cfg
        self.params = params
        self.max_new = max_new_tokens
        self.eos_id = eos_id
        self.pad_id = pad_id
        self._prefill = jax.jit(
            lambda p, t, fe: registry.prefill(
                cfg, p, t, frontend_embeds=fe,
                max_len=t.shape[1] + max_new_tokens))
        self._decode = jax.jit(
            lambda p, tok, cache: registry.decode_step(cfg, p, tok, cache),
            donate_argnums=(2,))

    def generate(self, prompts: jnp.ndarray,
                 frontend_embeds=None) -> Dict:
        """prompts (B, S) int32 -> dict with tokens (B, <=max_new) + stats.

        With an ``eos_id``, a lane that has emitted it is finished: its
        later positions hold ``pad_id`` (a finished lane's argmax is KV
        garbage, not output), ``tokens_out`` counts only tokens emitted
        by lanes still alive at step start, and decode exits as soon as
        every lane is done — ``per_token_ms`` divides by the decode
        steps actually executed, not the output width.
        """
        b = prompts.shape[0]
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, prompts,
                                      frontend_embeds)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(tok)
        t1 = time.perf_counter()
        t_np = np.asarray(tok)
        out = [t_np]
        alive = np.ones(b, bool)
        if self.eos_id is not None:
            alive &= t_np != self.eos_id
        n_out = b
        decode_steps = 0
        for _ in range(self.max_new - 1):
            if self.eos_id is not None and not alive.any():
                break
            logits, cache = self._decode(self.params, tok, cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            decode_steps += 1
            t_np = np.asarray(tok)
            if self.eos_id is not None:
                t_np = np.where(alive, t_np,
                                self.pad_id).astype(np.int32)
                n_out += int(alive.sum())
                alive &= t_np != self.eos_id
            else:
                n_out += b
            out.append(t_np)
        jax.block_until_ready(tok)
        t2 = time.perf_counter()
        tokens = np.stack(out, axis=1)
        stats = ServeStats(
            prefill_s=t1 - t0, decode_s=t2 - t1, tokens_out=n_out,
            per_token_ms=(t2 - t1) / max(decode_steps, 1) * 1e3,
            throughput_tok_s=n_out / max(t2 - t0, 1e-9),
            decode_steps=decode_steps)
        return {"tokens": tokens, "stats": stats}
