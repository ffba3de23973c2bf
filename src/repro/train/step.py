"""Train-step builder: microbatched gradient accumulation + AdamW, fully
sharded (FSDP over ``data``, TP over ``model``, DP over ``pod``+``data``).

The returned step is a plain function of (params, opt_state, step_idx,
batch) so it can be ``jax.jit``-ed with explicit in/out shardings by both
the real trainer (``repro.launch.train``) and the dry-run launcher.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import registry
from repro.models.common import ModelConfig, softmax_cross_entropy
from repro.optim import (AdamWConfig, adamw_init, adamw_update, lr_schedule,
                         opt_state_axes, opt_state_specs)
from repro.parallel import ctx as pctx
from repro.parallel import sharding as shd


def _loss_fn(cfg: ModelConfig, params, tokens, labels, frontend_embeds):
    if cfg.n_experts > 0:
        logits, aux = registry.forward(cfg, params, tokens,
                                       frontend_embeds=frontend_embeds,
                                       return_aux=True)
        ce = softmax_cross_entropy(logits[:, :-1], labels[:, 1:])
        return ce + cfg.router_aux_coef * aux, ce
    logits = registry.forward(cfg, params, tokens,
                              frontend_embeds=frontend_embeds)
    ce = softmax_cross_entropy(logits[:, :-1], labels[:, 1:])
    return ce, ce


def build_train_step(cfg: ModelConfig, *, n_microbatch: int = 1,
                     opt: AdamWConfig = AdamWConfig(),
                     lr_kwargs: Optional[dict] = None) -> Callable:
    """Returns step(params, opt_state, step_idx, batch) ->
    (params, opt_state, metrics).

    batch = {tokens (B,S), labels (B,S)[, frontend_embeds]}
    """
    lr_kwargs = lr_kwargs or {}

    def step(params, opt_state, step_idx, batch):
        tokens = batch["tokens"]
        b = tokens.shape[0]
        assert b % n_microbatch == 0, (b, n_microbatch)
        mb = b // n_microbatch

        def resh(x):
            y = x.reshape(n_microbatch, mb, *x.shape[1:])
            mesh = pctx.get_mesh()
            if mesh is not None:
                # Keep each microbatch sharded over ALL DP axes.  Without
                # this, GSPMD aligns the new n_mb dim with the pod axis
                # (pod p holds microbatch p) and every scan iteration then
                # computes a full microbatch replicated across pods —
                # verified 2x per-chip flops on the 2x16x16 mesh.
                ba = pctx.batch_axes(mesh)
                spec = P(None, ba if len(ba) > 1 else ba[0],
                         *([None] * (x.ndim - 1)))
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, spec))
            return y

        mbatch = jax.tree.map(resh, batch)
        zeros_like32 = lambda p: jnp.zeros(p.shape, jnp.float32)
        grad0 = jax.tree.map(zeros_like32, params)

        def mb_body(carry, mbx):
            gacc, lacc = carry
            fe = mbx.get("frontend_embeds")
            (_, ce), grads = jax.value_and_grad(
                lambda p: _loss_fn(cfg, p, mbx["tokens"], mbx["labels"],
                                   fe), has_aux=True)(params)
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            return (gacc, lacc + ce), None

        if n_microbatch == 1:
            mbx = jax.tree.map(lambda x: x[0], mbatch)
            (grads, loss), _ = mb_body((grad0, jnp.float32(0.0)), mbx)
        elif pctx.get_unroll():
            carry = (grad0, jnp.float32(0.0))
            for i in range(n_microbatch):
                mbx = jax.tree.map(lambda x: x[i], mbatch)
                carry, _ = mb_body(carry, mbx)
            grads, loss = carry
        else:
            (grads, loss), _ = jax.lax.scan(
                mb_body, (grad0, jnp.float32(0.0)), mbatch)
        grads = jax.tree.map(lambda g: g / n_microbatch, grads)
        loss = loss / n_microbatch

        lr = lr_schedule(step_idx, **lr_kwargs)
        params2, opt_state2, om = adamw_update(opt, grads, params,
                                               opt_state, lr)
        metrics = {"loss": loss, **om}
        return params2, opt_state2, metrics

    return step


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------


def train_state_shardings(cfg: ModelConfig, mesh,
                          rules: Optional[dict] = None):
    """(param_shardings, opt_shardings) for jit."""
    axes = registry.logical_axes(cfg)
    p_specs = registry.param_specs(cfg)
    p_sh = shd.shardings_from_axes(axes, mesh, rules, p_specs)
    o_sh = {
        "m": p_sh,
        "v": p_sh,
        "count": NamedSharding(mesh, P()),
    }
    return p_sh, o_sh


def batch_shardings(cfg: ModelConfig, mesh, specs: Dict) -> Dict:
    out = {}
    for k, s in specs.items():
        out[k] = shd.batch_sharding(mesh, ndim=len(s.shape))
    return out


def train_state_specs(cfg: ModelConfig):
    p_specs = registry.param_specs(cfg)
    return p_specs, opt_state_specs(p_specs)
