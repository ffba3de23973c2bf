"""Token-choice top-k Mixture-of-Experts FFN (olmoe-1b-7b,
moonshot-v1-16b-a3b).

Routing (router logits in float32 at full precision):

* ``scoring_func="softmax"`` (OLMoE): top-k of the softmax, renormalised.
* ``scoring_func="sigmoid"`` (DeepSeek-V3 ``noaux_tc`` with one group,
  Moonlight): experts are chosen by their sigmoid score plus a learned
  correction bias (``router_bias``); their weights are the unbiased
  scores, normalised over the k and scaled by ``routed_scaling``.

Shared experts (``n_shared_experts``) run on every token, as one SwiGLU
of width ``n_shared_experts * expert_ff``, added to the routed part.

A layer holds experts ``first_held_expert .. + held_experts`` of the
router's ``n_experts`` (all of them unless ``n_held_experts`` says
otherwise): it routes over all of them and computes its own experts'
part of the result, one chip's share of an expert-parallel deployment.
The absent experts' part is left out; adding the parts of every share,
with the shared experts counted once, gives the whole layer.

Two execution paths:

* ``moe_ffn_dropless`` (no mesh; training, prefill and decode): every
  routed slot is computed.  Slots are sorted by expert and run through
  grouped matmuls (``jax.lax.ragged_dot``, the TPU's ``ragged-dot``), so
  memory is bounded by tokens x top-k rows, never (experts, tokens, D).
  What a token receives never depends on the rest of its batch.  On a
  TPU the shared experts run as the named Pallas kernel
  ``kernels/moe.py`` ``moe_shared``, elsewhere as ``common.mlp_forward``,
  which the kernel equals.
* ``moe_ffn_sharded`` — expert-parallel ``shard_map`` for training on a
  mesh: tokens resharded over the ``model`` axis (sequence-split) ->
  local capacity dispatch (scatter, no (T,E,C) one-hot) -> ``all_to_all``
  over ``model`` (EP) -> per-expert SwiGLU (weights FSDP-gathered over
  ``data``) -> ``all_to_all`` back -> weighted combine.  Capacity:
  ``C = clamp(ceil(top_k * T / E * capacity_factor), 8, T*top_k)`` per
  shard; overflow tokens are dropped (GShard semantics) and their
  residual stream passes through unchanged.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import moe as kmoe
from repro.models import common as cm
from repro.models.common import ModelConfig
from repro.parallel import ctx as pctx

# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def moe_params(key, cfg: ModelConfig) -> dict:
    d, e, f, dt = cfg.d_model, cfg.held_experts, cfg.expert_ff, cfg.dtype
    ks = jax.random.split(key, 4)
    p = {
        "router": cm.dense_init(ks[0], (d, cfg.n_experts), jnp.float32),
        "w_gate": cm.dense_init(ks[1], (e, d, f), dt, in_axis=1),
        "w_up": cm.dense_init(ks[2], (e, d, f), dt, in_axis=1),
        "w_down": cm.dense_init(ks[3], (e, f, d), dt, in_axis=1),
    }
    if cfg.scoring_func == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = cm.mlp_params(jax.random.fold_in(key, 1), d,
                                    cfg.n_shared_experts * f, dt)
    return p


def moe_specs(cfg: ModelConfig) -> dict:
    d, e, f, dt = cfg.d_model, cfg.held_experts, cfg.expert_ff, cfg.dtype
    p = {
        "router": jax.ShapeDtypeStruct((d, cfg.n_experts), jnp.float32),
        "w_gate": jax.ShapeDtypeStruct((e, d, f), dt),
        "w_up": jax.ShapeDtypeStruct((e, d, f), dt),
        "w_down": jax.ShapeDtypeStruct((e, f, d), dt),
    }
    if cfg.scoring_func == "sigmoid":
        p["router_bias"] = jax.ShapeDtypeStruct((cfg.n_experts,),
                                                jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = cm.mlp_specs(d, cfg.n_shared_experts * f, dt)
    return p


def moe_axes(cfg: ModelConfig) -> dict:
    p = {
        "router": (None, None),
        "w_gate": ("expert", None, "expert_mlp"),
        "w_up": ("expert", None, "expert_mlp"),
        "w_down": ("expert", "expert_mlp", None),
    }
    if cfg.scoring_func == "sigmoid":
        p["router_bias"] = (None,)
    if cfg.n_shared_experts:
        p["shared"] = dict(cm.MLP_AXES)
    return p


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------


def router_logits(xt: jnp.ndarray, router: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(xt.astype(jnp.float32), router,
                   precision=jax.lax.Precision.HIGHEST)


def route(cfg: ModelConfig, p: dict, xt: jnp.ndarray):
    """xt (T, D) -> top-k ids (T,k), weights fp32 (T,k), aux loss scalar
    (Switch-style load balance over the scores' distribution)."""
    logits = router_logits(xt, p["router"])                   # (T, E)
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + p["router_bias"], cfg.top_k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
            * cfg.routed_scaling
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, cfg.top_k)              # (T, k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # Switch-style load-balancing loss: E * sum_e f_e * p_e
    f_e = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.float32),
                axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(f_e * p_e)
    return ids, w, aux


# ---------------------------------------------------------------------------
# Dispatch without drops
# ---------------------------------------------------------------------------


def held_experts(cfg: ModelConfig, p: dict, xt, ids, w) -> jnp.ndarray:
    """The held experts' weighted sum for tokens xt (T, D) routed to
    ``ids`` with weights ``w`` (T, k): each slot whose expert is held is
    computed, none is dropped.  Slots are sorted by expert (the others
    last), gathered, and run through grouped matmuls."""
    t, d = xt.shape
    k, e = ids.shape[1], cfg.held_experts
    local = ids.reshape(-1) - cfg.first_held_expert
    held = (local >= 0) & (local < e)
    key = jnp.where(held, local, e)
    order = jnp.argsort(key)                        # stable: token order
    sizes = jnp.bincount(key, length=e + 1)[:e].astype(jnp.int32)
    rows = jnp.take(xt, order // k, axis=0)         # (T*k, D)
    g = jax.lax.ragged_dot(rows, p["w_gate"], sizes)
    u = jax.lax.ragged_dot(rows, p["w_up"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    out = jax.lax.ragged_dot(h, p["w_down"], sizes)             # (T*k, D)
    # rows past the held slots belong to no group: select, never scale
    wk = jnp.take(w.reshape(-1), order)
    out = jnp.where(jnp.take(held, order)[:, None],
                    out.astype(jnp.float32) * wk[:, None], 0.0)
    y = jnp.take(out, jnp.argsort(order), axis=0)   # back to (token, slot)
    return y.reshape(t, k, d).sum(axis=1).astype(xt.dtype)


@jax.custom_vjp
def _shared_kernel(p: dict, xt):
    return kmoe.moe_shared(xt, p["w_gate"], p["w_up"], p["w_down"])


def _shared_kernel_fwd(p, xt):
    return _shared_kernel(p, xt), (p, xt)


def _shared_kernel_bwd(res, g):
    # the kernel has no reverse rule; its gradient is mlp_forward's
    return jax.vjp(cm.mlp_forward, *res)[1](g)


_shared_kernel.defvjp(_shared_kernel_fwd, _shared_kernel_bwd)


def shared_experts(p: dict, xt) -> jnp.ndarray:
    """The shared experts' SwiGLU of tokens xt (T, D): the named kernel
    on a TPU, so the profiler names its device time, else the jnp form."""
    if jax.default_backend() == "tpu":
        return _shared_kernel(p, xt)
    return cm.mlp_forward(p, xt)


def moe_ffn_dropless(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    """x (B, S, D) -> (held and shared experts' output, aux loss)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    ids, w, aux = route(cfg, p, xt)
    y = held_experts(cfg, p, xt, ids, w)
    if cfg.n_shared_experts:
        y = y + shared_experts(p["shared"], xt)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Capacity dispatch (the expert-parallel path)
# ---------------------------------------------------------------------------


def _capacity(t: int, cfg: ModelConfig) -> int:
    c = math.ceil(cfg.top_k * t / cfg.n_experts * cfg.capacity_factor)
    return max(8, min(c, t * cfg.top_k))


def _dispatch_indices(ids: jnp.ndarray, t: int, cap: int, cfg: ModelConfig):
    """Position of each (token, slot) within its expert's capacity buffer.

    Returns flat scatter indices (T*k,) into (E*cap) with dropped slots
    mapped to E*cap (out of bounds -> scatter 'drop' mode)."""
    flat = ids.reshape(-1)                                    # (T*k,) token-major
    onehot = jax.nn.one_hot(flat, cfg.n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1             # (T*k, E)
    pos = jnp.sum(pos * onehot, axis=-1)                      # (T*k,)
    keep = pos < cap
    idx = flat * cap + pos
    return jnp.where(keep, idx, cfg.n_experts * cap), keep


def _expert_ffn(buf: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """buf (E, C, D) x weights (E, D, F)/(E, F, D) -> (E, C, D)."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _local_moe(xt, p, cfg: ModelConfig, cap: int,
               ffn=_expert_ffn) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full dispatch->ffn->combine on local tokens xt (T, D)."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    ids, w, aux = route(cfg, p, xt)
    idx, keep = _dispatch_indices(ids, t, cap, cfg)
    xt_rep = jnp.repeat(xt, k, axis=0)                        # (T*k, D)
    buf = jnp.zeros((e * cap, d), xt.dtype)
    buf = buf.at[idx].set(xt_rep, mode="drop")
    buf = buf.reshape(e, cap, d)
    out = ffn(buf, p["w_gate"], p["w_up"], p["w_down"])       # (E, C, D)
    out = out.reshape(e * cap, d)
    gathered = jnp.take(out, jnp.minimum(idx, e * cap - 1), axis=0)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    y = (gathered.reshape(t, k, d).astype(jnp.float32)
         * w[:, :, None]).sum(axis=1)
    return y.astype(xt.dtype), aux


# ---------------------------------------------------------------------------
# Sharded (expert-parallel) path
# ---------------------------------------------------------------------------


def moe_ffn_sharded(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    """x (B, S, D) global.  Requires an active mesh (see parallel.ctx).
    Routed experts only; the layer holds every expert."""
    if cfg.held_experts != cfg.n_experts:
        raise ValueError("the expert-parallel path shards every expert "
                         "over the mesh; it cannot hold a share of them")
    mesh = pctx.get_mesh()
    axes = mesh.axis_names
    batch_ax = pctx.batch_axes(mesh)          # ('pod','data') or ('data',)
    mdl = "model"
    m = mesh.shape[mdl]
    b, s, d = x.shape
    shard_seq = (s % m == 0) and s >= m and s > 1
    # per-shard token count
    dp = math.prod(mesh.shape[a] for a in batch_ax)
    t_loc = (b // dp) * (s // m if shard_seq else s)
    cap = _capacity(max(t_loc, 1), cfg)

    x_spec = P(batch_ax, mdl, None) if shard_seq else P(batch_ax, None, None)
    w_specs = {
        "router": P(None, None),
        "router_bias": P(None),
        "w_gate": P(mdl, None, "data"),
        "w_up": P(mdl, None, "data"),
        "w_down": P(mdl, "data", None),
    }

    def local_fn(xl, pl):
        bl, sl, _ = xl.shape
        xt = xl.reshape(bl * sl, d)
        # FSDP-gather expert weights over 'data'
        pg = dict(pl)
        pg["w_gate"] = jax.lax.all_gather(pl["w_gate"], "data", axis=2,
                                          tiled=True)
        pg["w_up"] = jax.lax.all_gather(pl["w_up"], "data", axis=2,
                                        tiled=True)
        pg["w_down"] = jax.lax.all_gather(pl["w_down"], "data", axis=1,
                                          tiled=True)

        def ep_ffn(buf, wg, wu, wd):
            # buf (E, C, D) -> a2a -> (E/m, C*m, D) -> ffn -> a2a back
            buf = jax.lax.all_to_all(buf, mdl, split_axis=0, concat_axis=1,
                                     tiled=True)
            out = _expert_ffn(buf, wg, wu, wd)
            return jax.lax.all_to_all(out, mdl, split_axis=1, concat_axis=0,
                                      tiled=True)

        y, aux = _local_moe(xt, pg, cfg, cap, ffn=ep_ffn)
        # aux varies over the axes that shard tokens; pmean only those
        # (when S is not sharded, aux is model-invariant already).
        aux_axes = batch_ax + ((mdl,) if shard_seq else ())
        aux = jax.lax.pmean(aux, aux_axes)
        return y.reshape(bl, sl, d), aux

    # check_vma=False: when S is not sharded (decode), every model rank
    # computes identical dispatch and the a2a round trip reassembles the
    # full (E, C, D) buffer identically on each rank — replicated by
    # construction, but not statically inferable through all_to_all.
    routed = {k: v for k, v in p.items() if k in w_specs}
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, {k: w_specs[k] for k in routed}),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, routed)
    return y, aux


def moe_ffn(cfg: ModelConfig, p: dict, x: jnp.ndarray):
    """The expert-parallel path when a mesh is active, else the dropless
    one.  Returns (output, aux loss)."""
    if pctx.get_mesh() is None:
        return moe_ffn_dropless(cfg, p, x)
    y, aux = moe_ffn_sharded(cfg, p, x)
    if cfg.n_shared_experts:
        y = y + cm.mlp_forward(p["shared"], x)
    return y, aux
