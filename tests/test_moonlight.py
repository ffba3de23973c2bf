"""Moonlight-16B-A3B (DeepSeek-V3: latent attention, dense-then-expert
stack, sigmoid routing with a correction bias, shared experts, one
chip's share of the experts) against the plain float32 reference of the
benchmark (``bench/reference/moonlight.py``), on seeded random weights
at a small size on the CPU (where the model runs its jnp forms), and
the kernels a TPU runs instead against them (interpret mode).

Tolerances: where both sides compute in float32 the same mathematics in
another order (latent space against decompressed heads, online against
one-pass softmax, chunks against whole), 1e-4 relative and absolute
(float32 rounding over a few layers, four orders above it and three
below any real error at these scales); routing ids are compared
exactly, routing weights at 1e-6 (float32 sums of the same few scores);
bfloat16 kernel outputs at 2e-2 (bfloat16 keeps 8 bits).
"""
import dataclasses
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from drivers import live_serve_mla as drv  # noqa: E402
from reference import moonlight as ref  # noqa: E402

from repro.kernels import moe as kmoe  # noqa: E402
from repro.kernels.mla_decode import mla_decode_attention  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import common as cm  # noqa: E402
from repro.models import mla, moe, registry  # noqa: E402

F32_TOL = dict(rtol=1e-4, atol=1e-4)


def small(held=4, first=4, experts=16, top_k=4):
    """The configuration file's keys at a small size: 3 layers (1 dense,
    2 expert), ``held`` of ``experts`` experts from ``first``.
    initializer_range 0.125 gives each projection's outputs the scale
    they have at full size (sqrt(width) x std near 1)."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "moonlight_16b_a3b.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, kv_lora_rank=32, v_head_dim=16,
               intermediate_size=128, moe_intermediate_size=32,
               num_hidden_layers=3, vocab_size=256, n_routed_experts=held,
               num_experts_per_tok=top_k, initializer_range=0.125)
    cfg["deployment"] = dict(cfg["deployment"], n_routed_experts=experts,
                             first_held_expert=first)
    return cfg


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def program(cfg, w):
    """(float32 ModelConfig, float32 params) of the served model."""
    mc = dataclasses.replace(drv.model_config(cfg), dtype=jnp.float32)
    return mc, drv.program_params(f32(w))


@pytest.fixture(scope="module")
def model():
    cfg = small()
    return cfg, drv.make_weights(cfg, 11)


def test_program_params_match_the_model_specs(model):
    cfg, w = model
    mc = drv.model_config(cfg)
    got = jax.tree.map(lambda a: (a.shape, a.dtype),
                       drv.program_params(w))
    want = jax.tree.map(lambda s: (s.shape, s.dtype),
                        registry.param_specs(mc))
    assert got == want


def test_published_widths_and_held_share():
    from repro import configs
    full = drv.model_config(json.loads(
        (ROOT / "bench" / "configs" / "moonlight_16b_a3b.json").read_text()))
    assert full.n_params() == 3_364_615_296       # one EP8 chip's share
    pub = dataclasses.replace(full, n_held_experts=0)
    assert pub.n_params() == 15_960_110_208       # the published model
    c = configs.get("moonshot_v1_16b_a3b")
    assert dataclasses.replace(c, name=pub.name, dtype=pub.dtype,
                               remat=False) == pub


@pytest.mark.parametrize("decode_steps", [1, 5])
def test_prefill_then_decode_matches_the_reference(model, decode_steps):
    """Prefill, then decoding through the latent cache, gives the
    reference's full-forward logits at every position."""
    cfg, w = model
    mc, params = program(cfg, w)
    s = 12
    tokens = np.random.default_rng(3).integers(0, 256, (2, s + decode_steps))
    want = np.asarray(ref.logits(cfg, w, tokens, s - 1))
    logits, cache = registry.prefill(mc, params, jnp.asarray(tokens[:, :s]),
                                     max_len=s + decode_steps)
    got = [logits]
    for i in range(decode_steps):
        logits, cache = registry.decode_step(
            mc, params, jnp.asarray(tokens[:, s + i]), cache)
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], axis=1)
    np.testing.assert_allclose(got, want, **F32_TOL)
    assert int(cache["len"]) == s + decode_steps


def test_forward_matches_the_reference(model):
    cfg, w = model
    mc, params = program(cfg, w)
    tokens = np.random.default_rng(4).integers(0, 256, (2, 20))
    with jax.default_matmul_precision("highest"):
        got = registry.forward(mc, params, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.logits(cfg, w, tokens, 0)),
                               **F32_TOL)


def test_absorbed_decode_equals_decompressed_attention(model):
    """One layer: the latent-space decode of the last position against
    the full attention with keys and values decompressed."""
    cfg, w = model
    mc, params = program(cfg, w)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    s, b = 9, 3
    h = jax.random.normal(jax.random.PRNGKey(0), (b, s, 64), jnp.float32)
    full, c, k_pe = mla.attend(mc, lp, h, jnp.arange(s))
    spec = registry.cache_specs(mc, b, s)
    ckv = jnp.zeros(spec["ckv"].shape, jnp.float32).at[1, :, :s - 1].set(
        c[:, :s - 1])
    kpe = jnp.zeros(spec["kpe"].shape, jnp.float32).at[1, :, :, :s - 1].set(
        k_pe[:, :s - 1].swapaxes(1, 2))
    out, ckv, kpe = mla.decode(mc, lp, h[:, s - 1:], ckv, kpe, 1, s - 1)
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, s - 1]), **F32_TOL)
    np.testing.assert_allclose(np.asarray(ckv[1, :, :s]), np.asarray(c),
                               **F32_TOL)
    np.testing.assert_allclose(np.asarray(kpe[1, :, :, :s]),
                               np.asarray(k_pe.swapaxes(1, 2)), **F32_TOL)
    assert not np.asarray(ckv[0]).any() and not np.asarray(ckv[2]).any()


def _routing_inputs(seed, t=64, d=64, e=16, bias_std=0.05):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    xt = jax.random.normal(k1, (t, d), jnp.float32)
    router = jax.random.normal(k2, (d, e), jnp.float32) * 0.125
    bias = jax.random.normal(k3, (e,), jnp.float32) * bias_std
    return xt, router, bias


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_routing_matches_the_reference(seed):
    """Ids and normalised, scaled weights of the program's route against
    the reference's dense gates; and the bias changes which experts are
    chosen but not how they are weighted."""
    cfg = small()
    mc = drv.model_config(cfg)
    xt, router, bias = _routing_inputs(seed)
    p = {"router": router, "router_bias": bias}
    ids, w, _ = moe.route(mc, p, xt)
    np.testing.assert_allclose(np.asarray(w).sum(-1), mc.routed_scaling,
                               rtol=1e-6)
    # the reference's gate of every expert, all 16 held
    with jax.default_matmul_precision("highest"):
        gate = ref.routing(xt, router, bias, top_k=mc.top_k,
                           scale=mc.routed_scaling, first=0, held=16)
    dense = np.zeros((xt.shape[0], 16), np.float32)
    np.put_along_axis(dense, np.asarray(ids), np.asarray(w), axis=1)
    np.testing.assert_allclose(dense, np.asarray(gate), rtol=1e-6,
                               atol=1e-7)
    # without the bias other experts win, with weights from the same
    # unbiased scores
    ids0, w0, _ = moe.route(mc, {"router": router,
                                 "router_bias": jnp.zeros_like(bias)}, xt)
    assert (np.sort(np.asarray(ids0), -1) != np.sort(np.asarray(ids), -1)
            ).any()
    scores = np.asarray(jax.nn.sigmoid(moe.router_logits(xt, router)))
    for ii, ww in ((ids, w), (ids0, w0)):
        s = np.take_along_axis(scores, np.asarray(ii), -1)
        np.testing.assert_allclose(
            np.asarray(ww), s / s.sum(-1, keepdims=True) * 2.446,
            rtol=1e-6)


def _dense_held(mc, p, xt, ids, w):
    """Every held expert over every token, weighted by its routing
    weight: what a drop-free dispatch must give."""
    out = jnp.zeros(xt.shape, jnp.float32)
    for e in range(mc.held_experts):
        y = jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e]) \
            @ p["w_down"][e]
        g = jnp.sum(jnp.where(ids == mc.first_held_expert + e, w, 0.0), -1)
        out = out + g[:, None] * y
    return out


@pytest.mark.parametrize("tokens", [1, 7, 64, 300])
@pytest.mark.parametrize("skew", [False, True])
def test_no_slot_is_dropped(tokens, skew):
    """The drop-free dispatch computes every slot on a held expert, at
    any batch, and when every token routes to the same held experts
    (a capacity dispatch would drop most of them)."""
    cfg = small(held=4, first=4)
    mc = drv.model_config(cfg)
    xt, router, bias = _routing_inputs(tokens, t=tokens)
    if skew:
        bias = bias.at[4:8].set(10.0)        # held experts always chosen
    k = jax.random.split(jax.random.PRNGKey(99), 3)
    p = {"router": router, "router_bias": bias,
         "w_gate": jax.random.normal(k[0], (4, 64, 32)) * 0.125,
         "w_up": jax.random.normal(k[1], (4, 64, 32)) * 0.125,
         "w_down": jax.random.normal(k[2], (4, 32, 64)) * 0.18}
    ids, w, _ = moe.route(mc, p, xt)
    if skew:
        assert (np.sort(np.asarray(ids), -1) == [4, 5, 6, 7]).all()
    got = moe.held_experts(mc, p, xt, ids, w)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_held(mc, p, xt, ids, w)),
                               **F32_TOL)
    # in chunks, as a long prefill runs, the same
    chunked = cm.map_token_chunks(
        lambda r: moe.held_experts(mc, p, *r), (xt, ids, w), 3)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(got),
                               **F32_TOL)


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of an EP8 deployment each hold 2 of 16 experts; the
    parts of the result their layers give, with the shared experts
    counted once, add up to what the reference gives for the whole
    layer."""
    whole = small(held=16, first=0)
    w = drv.make_weights(whole, 5)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 64), jnp.float32)
    mw = jax.tree.map(lambda a: a[0], f32(w["moe"]))
    mc_whole = drv.model_config(whole)
    shared = {"w_gate": mw["s_gate"], "w_up": mw["s_up"],
              "w_down": mw["s_down"]}

    def share(i):
        cfg = small(held=2, first=2 * i)
        mc = dataclasses.replace(drv.model_config(cfg), dtype=jnp.float32)
        sl = slice(2 * i, 2 * i + 2)
        p = {"router": mw["router"], "router_bias": mw["router_bias"],
             "w_gate": mw["e_gate"][sl], "w_up": mw["e_up"][sl],
             "w_down": mw["e_down"][sl], "shared": shared}
        y, _ = moe.moe_ffn(mc, p, x)
        return y

    parts = sum(share(i) for i in range(8))
    with jax.default_matmul_precision("highest"):
        shared_y = ref._swiglu(x, mw["s_gate"], mw["s_up"], mw["s_down"],
                               "f32")
        gate = ref.routing(x, mw["router"], mw["router_bias"],
                           top_k=mc_whole.top_k,
                           scale=mc_whole.routed_scaling, first=0, held=16)
        g = jnp.einsum("bsd,edf->bsef", x, mw["e_gate"])
        u = jnp.einsum("bsd,edf->bsef", x, mw["e_up"])
        per = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g) * u, mw["e_down"])
        uncut = jnp.einsum("bse,bsed->bsd", gate, per) + shared_y
    np.testing.assert_allclose(np.asarray(parts - 7 * shared_y),
                               np.asarray(uncut), **F32_TOL)


# -- kernels against their oracles ---------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16,
                                        dict(rtol=2e-2, atol=2e-2))])
@pytest.mark.parametrize("b,t,length,block", [
    (2, 128, 1, 128), (3, 128, 77, 128), (4, 384, 300, 128),
    (16, 256, 256, 64), (20, 256, 129, 128)])
def test_mla_decode_kernel_matches_decode_attention(b, t, length, block,
                                                    dtype, tol):
    """The kernel against ``attention.decode_attention`` with one KV head
    of ``[c_kv ‖ k_pe]``, values ``c_kv`` and the decompressed head's
    scale; positions past ``length`` hold large garbage."""
    h, r, p, layers = 4, 32, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(b * t + length), 4)
    q_lat = jax.random.normal(ks[0], (b, h, r), dtype)
    q_pe = jax.random.normal(ks[1], (b, h, p), dtype)
    ckv = jax.random.normal(ks[2], (layers, b, t, r), jnp.float32)
    kpe = jax.random.normal(ks[3], (layers, b, p, t), jnp.float32)
    ckv = ckv.at[:, :, length:].set(1e3).astype(dtype)
    kpe = kpe.at[:, :, :, length:].set(1e3).astype(dtype)
    scale = 24 ** -0.5
    got = mla_decode_attention(q_lat, q_pe, ckv, kpe, 1, length,
                               scale=scale, block_t=block, interpret=True)
    q = jnp.concatenate([q_lat, q_pe], -1)[:, None]
    key = jnp.concatenate([ckv[1], kpe[1].swapaxes(1, 2)], -1)[:, :, None]
    want = attn.decode_attention(q, key, ckv[1][:, :, None], length,
                                 scale=scale)[:, 0]
    assert got.shape == (b, h, r) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("t", [2, 64, 1024])
def test_shared_kernel_matches_the_swiglu(t):
    k = jax.random.split(jax.random.PRNGKey(t), 4)
    x = jax.random.normal(k[0], (t, 64), jnp.float32)
    p = {"w_gate": jax.random.normal(k[1], (64, 512)) * 0.125,
         "w_up": jax.random.normal(k[2], (64, 512)) * 0.125,
         "w_down": jax.random.normal(k[3], (512, 64)) * 0.05}
    got = kmoe.moe_shared(x, p["w_gate"], p["w_up"], p["w_down"],
                          interpret=True)
    from repro.models.common import mlp_forward
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(mlp_forward(p, x)), **F32_TOL)


def test_shared_kernel_takes_the_swiglus_gradient(monkeypatch):
    """On a TPU the shared experts run as the kernel, which has no
    reverse rule of its own: a gradient through them is
    ``mlp_forward``'s."""
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(k[0], (16, 64), jnp.float32)
    p = {"w_gate": jax.random.normal(k[1], (64, 256)) * 0.125,
         "w_up": jax.random.normal(k[2], (64, 256)) * 0.125,
         "w_down": jax.random.normal(k[3], (256, 64)) * 0.05}
    want = jax.grad(lambda p, x: cm.mlp_forward(p, x).sum(),
                    argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kmoe, "moe_shared", functools.partial(
        kmoe.moe_shared, interpret=True))
    got = jax.grad(lambda p, x: moe.shared_experts(p, x).sum(),
                   argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **F32_TOL)


@pytest.mark.parametrize("first,held,want", [
    (0, 1, 0.25),       # chosen: 0.9 less the best unchosen, 0.65
    (2, 1, 0.15),       # unchosen: the last chosen, 0.75, less 0.6
    (1, 2, 0.10),       # the nearer of 0.75 - 0.65 and 0.75 - 0.6
])
def test_routing_margin_by_hand(first, held, want):
    """Biased scores of 5 experts, top 2 (experts 0 and 1 chosen): how
    far the held experts lie from the boundary of the chosen."""
    c = jnp.asarray([[0.9, 0.75, 0.6, 0.65, 0.2]])
    logits = jnp.log(c / (1 - c))               # sigmoid gives c back
    got = ref.routing_margin(logits, jnp.eye(5), jnp.zeros(5), top_k=2,
                             first=first, held=held)
    np.testing.assert_allclose(np.asarray(got), [want], rtol=1e-5)


# -- the dense decoder is unchanged ---------------------------------------------


def _decode_attention_pr15(q, k_cache, v_cache, cache_len):
    """``attention.decode_attention`` as it was before it took an
    explicit scale and a value width of its own."""
    b, _, h, hd = q.shape
    sk, hkv = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(b, hkv, h // hkv, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) \
        * scale
    kpos = jnp.arange(sk)
    cache_len = jnp.asarray(cache_len)
    if cache_len.ndim == 0:
        valid = jnp.broadcast_to(kpos[None, :] < cache_len, (b, sk))
    else:
        valid = kpos[None, :] < cache_len[:, None]
    s = jnp.where(valid[:, None, None, :], s, attn.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bgkd", p.astype(v_cache.dtype), v_cache)
    return o.transpose(0, 2, 1, 3).reshape(b, 1, h, hd)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_qwen3_decode_step_is_bit_identical(dtype, monkeypatch):
    """The qwen3 smoke model's decode step gives the same bits, logits
    and cache, through the generalised ``decode_attention`` as through
    the form it had before."""
    from repro import configs
    cfg = dataclasses.replace(configs.get_smoke("qwen3_4b"), dtype=dtype,
                              remat=False)
    key = jax.random.PRNGKey(0)
    params = registry.init(cfg, key)
    tokens = jax.random.randint(key, (2, 12), 0, cfg.vocab)
    _, cache = registry.prefill(cfg, params, tokens, max_len=20)

    def step():
        return jax.jit(functools.partial(registry.decode_step, cfg))(
            params, tokens[:, -1], cache)
    now = step()
    monkeypatch.setattr(attn, "decode_attention", _decode_attention_pr15)
    before = step()
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
