"""The served decode step updates its cache in place.

``BatchServer._decode`` donates the cache, and ``transformer.decode_step``
carries the stacked cache through the layer scan, each layer writing only
its new row (dense K/V stored ``(L, B, Hkv, T, hd)``).  The mathematics is
unchanged, so on the CPU the served tokens and logits are bitwise those
of the same steps without donation and of the step in the form it had
before: the layer scan taking each layer's cache as input and re-emitting
it whole (kept below as the reference).  The compiled form at the
benchmark's shapes is checked in ``test_tpu_compile.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import gqa, mla, registry, transformer
from repro.serve.loop import BatchServer

#: a dense GQA decoder and a latent-attention expert model
ARCHS = ["qwen3_4b", "moonshot_v1_16b_a3b"]
STEPS = 5


def _config(arch, dtype=jnp.bfloat16):
    return dataclasses.replace(configs.get_smoke(arch), dtype=dtype,
                               remat=False)


def _prompts(cfg, b=3, s=7):
    return jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)


def _xs_ys_step(cfg, params, token, cache):
    """One decode step with each layer's cache as the scan's input and
    its whole updated slice as the scan's output.  Dense caches in the
    ``(L, B, S, Hkv, hd)`` order of that form."""
    x = jnp.take(params["embed"], token[:, None], axis=0)
    pos = cache["len"]
    positions = jnp.reshape(pos, (1,))

    def dense(xc, layer_in):
        lp, kc, vc = layer_in
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if cfg.qk_norm:
            q = cm.head_rms_norm(q, lp["q_norm"], cfg.norm_eps)
            k = cm.head_rms_norm(k, lp["k_norm"], cfg.norm_eps)
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k = cm.apply_rope(k, positions, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos, axis=1)
        o = attn.decode_attention(q, kc, vc, pos + 1)
        xc = xc + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        xc, _ = transformer._ffn_block(cfg, lp, xc)
        return xc, (kc, vc)

    def latent(xc, layer_in):
        lp, ckv, kpe = layer_in
        h = cm.rms_norm(xc, lp["ln1"], cfg.norm_eps)
        o, c = mla.decode(cfg, lp, h, {"ckv": ckv[None], "kpe": kpe[None]},
                          0, pos)
        xc, _ = transformer._ffn_block(cfg, lp, xc + o)
        return xc, (c["ckv"][0], c["kpe"][0])

    keys = ("ckv", "kpe") if cfg.kv_lora_rank else ("k", "v")
    first, out = 0, []
    for name, n, _ in transformer._groups(cfg):
        xs = tuple(cache[k][first:first + n] for k in keys)
        x, ys = jax.lax.scan(latent if cfg.kv_lora_rank else dense, x,
                             (params[name],) + xs)
        out.append(ys)
        first += n
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.dot(x[:, 0, :], params["lm_head"]).astype(jnp.float32)
    new = {k: jnp.concatenate([ys[j] for ys in out])
           for j, k in enumerate(keys)}
    return logits, dict(new, len=pos + 1)


def _old_order(cache):
    """A dense cache ``(L, B, Hkv, T, hd)`` -> ``(L, B, T, Hkv, hd)``."""
    if "k" not in cache:
        return cache
    t = lambda a: a.swapaxes(2, 3)
    return dict(cache, k=t(cache["k"]), v=t(cache["v"]))


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    cfg = _config(request.param)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    return BatchServer(cfg, params, max_new_tokens=STEPS + 1)


def test_generate_matches_undonated_and_xs_ys_steps(served):
    """Same tokens from the donated step, from the same step jitted
    without donation, and from the xs/ys reference loop; the logits of
    every step are bitwise those of the reference."""
    cfg, params = served.cfg, served.params
    prompts = _prompts(cfg)
    got = served.generate(prompts)["tokens"]

    plain = BatchServer(cfg, params, max_new_tokens=STEPS + 1)
    plain._decode = jax.jit(
        lambda p, t, c: registry.decode_step(cfg, p, t, c))
    np.testing.assert_array_equal(got, plain.generate(prompts)["tokens"])

    ref_step = jax.jit(lambda p, t, c: _xs_ys_step(cfg, p, t, c))
    logits, cache = served._prefill(params, prompts, None)
    ref_cache = _old_order(cache)
    toks = [jnp.argmax(logits, axis=-1).astype(jnp.int32)]
    for _ in range(STEPS):
        want, ref_cache = ref_step(params, toks[-1], ref_cache)
        logits, cache = served._decode(params, toks[-1], cache)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
        toks.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
    np.testing.assert_array_equal(got, np.stack(toks, axis=1))
    for a, b in zip(jax.tree.leaves(_old_order(cache)),
                    jax.tree.leaves(ref_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_consumes_its_cache(served):
    prompts = _prompts(served.cfg)
    logits, cache = served._prefill(served.params, prompts, None)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _, new = served._decode(served.params, tok, cache)
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    assert not any(a.is_deleted() for a in jax.tree.leaves(new))
    assert int(new["len"]) == prompts.shape[1] + 1


@pytest.mark.parametrize("arch", ["qwen3_4b", "glm4_9b", "olmoe_1b_7b"])
def test_dense_cache_layout_and_decode_match_forward(arch):
    """``prefill`` lays the K/V cache out as ``cache_specs`` says,
    ``(L, B, Hkv, T, hd)`` with ``T`` the multiple of 16 at or above
    ``max_len``, and several decode steps give
    ``forward``'s logits at each new position (float32, 1e-4: the same
    mathematics in another order, as ``test_smoke_archs`` holds it)."""
    cfg = _config(arch, jnp.float32)
    if cfg.n_experts:      # no capacity drops between the two paths
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    b, s, max_len = 2, 6, 12
    tokens = jax.random.randint(jax.random.PRNGKey(2), (b, s + STEPS), 0,
                                cfg.vocab)
    full = registry.forward(cfg, params, tokens)
    _, cache = registry.prefill(cfg, params, tokens[:, :s], max_len=max_len)
    spec = registry.cache_specs(cfg, b, max_len)
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), cache)
            == jax.tree.map(lambda a: (a.shape, a.dtype), spec))
    assert cache["k"].shape == (cfg.n_layers, b, cfg.n_kv_heads, 16,
                                cfg.hd)
    assert registry.cache_axes(cfg)["k"] == ("layer", "batch", None,
                                             "kv_seq", None)
    step = jax.jit(lambda p, t, c: registry.decode_step(cfg, p, t, c))
    for i in range(STEPS):
        logits, cache = step(params, tokens[:, s + i], cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, s + i]),
                                   rtol=1e-4, atol=1e-4)
    assert int(cache["len"]) == s + STEPS


def _gqa_expected(cfg, lp, h, cache, layer, pos):
    """Grouped-query decode over the rows before ``pos`` and the new one."""
    q, k, v = gqa._project(cfg, lp, h, jnp.reshape(pos, (1,)))
    ks, vs = (cache[n][layer].swapaxes(1, 2)[:, :pos + 1].at[:, pos].set(
        new[:, 0]) for n, new in (("k", k), ("v", v)))
    o = attn.decode_attention(q, ks, vs, pos + 1)
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), {"k": k, "v": v}


def _mla_expected(cfg, lp, h, cache, layer, pos):
    """Absorbed latent decode over the rows before ``pos`` and the new
    one: one KV head of ``[c_kv || k_pe]``, values ``c_kv``."""
    nope = cfg.qk_nope_head_dim
    q_nope, q_pe, c, k_pe = mla._project(cfg, lp, h, jnp.reshape(pos, (1,)))
    cs = cache["ckv"][layer][:, :pos + 1].at[:, pos].set(c[:, 0])
    pes = cache["kpe"][layer].swapaxes(1, 2)[:, :pos + 1].at[:, pos].set(
        k_pe[:, 0])
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], lp["wkv_b"][..., :nope])
    q = jnp.concatenate([q_lat, q_pe[:, 0]], -1)[:, None]
    k = jnp.concatenate([cs, pes], -1)[:, :, None]
    o = attn.decode_attention(q, k, cs[:, :, None], pos + 1,
                              scale=mla.scale(cfg))[:, 0]
    o = jnp.einsum("bhr,rhv->bhv", o, lp["wkv_b"][..., nope:])
    return (jnp.einsum("bhv,hvd->bd", o, lp["wo"])[:, None],
            {"ckv": c, "kpe": k_pe})


#: (module, smoke config, expected output and rows, where the new row of
#: each cache array lies)
_MODULES = {
    "gqa": (gqa, "qwen3_4b", _gqa_expected,
            lambda layer, pos: {"k": (layer, slice(None), slice(None), pos),
                                "v": (layer, slice(None), slice(None), pos)}),
    "mla": (mla, "moonshot_v1_16b_a3b", _mla_expected,
            lambda layer, pos: {"ckv": (layer, slice(None), pos),
                                "kpe": (layer, slice(None), slice(None),
                                        pos)}),
}


@pytest.mark.parametrize("kind", list(_MODULES))
def test_attention_decode_writes_only_its_row(kind):
    """One ``decode`` at ``(layer, pos)`` into a cache filled with
    garbage: every cache element but that layer's row ``pos`` is
    bit-unchanged, the row holds the position's projection, and the
    output is ``attention.decode_attention`` over positions ``<= pos``
    (the garbage beyond them and in other layers is never read)."""
    module, arch, expected, row_at = _MODULES[kind]
    cfg = _config(arch, jnp.float32)
    params = registry.init(cfg, jax.random.PRNGKey(0))
    layer, pos, b = 1, 9, 2
    lp = jax.tree.map(lambda a: a[layer - cfg.first_k_dense],
                      params["layers"])
    spec = module.cache_specs(cfg, b, 20)
    keys = jax.random.split(jax.random.PRNGKey(3), len(spec) + 1)
    cache = {n: 1e3 * jax.random.normal(k, a.shape, a.dtype)
             for k, (n, a) in zip(keys, sorted(spec.items()))}
    h = jax.random.normal(keys[-1], (b, 1, cfg.d_model), jnp.float32)

    out, new = jax.jit(lambda c: module.decode(cfg, lp, h, c, layer, pos))(
        dict(cache))
    want, rows = expected(cfg, lp, h, cache, layer, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for n, at in row_at(layer, pos).items():
        got, old = np.asarray(new[n]), np.asarray(cache[n])
        keep = np.ones(old.shape, bool)
        keep[at] = False
        np.testing.assert_array_equal(got[keep], old[keep])
        np.testing.assert_allclose(got[at], np.asarray(rows[n][:, 0]),
                                   rtol=1e-6, atol=1e-6)
