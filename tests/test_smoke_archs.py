"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, asserting output shapes + no NaNs, plus prefill/decode consistency
against the parallel forward pass (a strong end-to-end correctness check
for every cache implementation)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import registry
from repro.models.common import softmax_cross_entropy

ARCHS = configs.ARCHS


def _inputs(cfg, key, batch=2, seq=16):
    kt, kf = jax.random.split(key)
    tokens = jax.random.randint(kt, (batch, seq), 0, cfg.vocab)
    fe = None
    if cfg.frontend == "patch":
        nf = min(cfg.n_frontend_tokens, seq // 2)
        cfg = dataclasses.replace(cfg, n_frontend_tokens=nf)
        fe = jax.random.normal(kf, (batch, nf, cfg.frontend_dim),
                               jnp.float32)
    elif cfg.frontend == "audio":
        from repro.models import encdec

        fe = jax.random.normal(kf, (batch, encdec.enc_len(cfg, seq),
                                    cfg.frontend_dim), jnp.float32)
    return cfg, tokens, fe


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_no_nans(arch, rng):
    cfg = configs.get_smoke(arch)
    cfg, tokens, fe = _inputs(cfg, rng)
    params = registry.init(cfg, rng)
    logits = registry.forward(cfg, params, tokens, frontend_embeds=fe)
    assert logits.shape == (*tokens.shape, cfg.vocab)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch, rng):
    cfg = configs.get_smoke(arch)
    cfg, tokens, fe = _inputs(cfg, rng)
    params = registry.init(cfg, rng)

    def loss_fn(p):
        logits = registry.forward(cfg, p, tokens, frontend_embeds=fe)
        return softmax_cross_entropy(logits[:, :-1], tokens[:, 1:])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert bool(jnp.all(jnp.isfinite(g))), "non-finite grad"


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_match_init(arch, rng):
    cfg = configs.get_smoke(arch)
    params = registry.init(cfg, rng)
    specs = registry.param_specs(cfg)
    flat_p = jax.tree.leaves_with_path(params)
    flat_s = jax.tree.leaves_with_path(specs)
    assert len(flat_p) == len(flat_s)
    for (kp, vp), (ks, vs) in zip(flat_p, flat_s):
        assert kp == ks
        assert vp.shape == vs.shape, f"{kp}: {vp.shape} != {vs.shape}"
        assert vp.dtype == vs.dtype, f"{kp}: {vp.dtype} != {vs.dtype}"
    axes = registry.logical_axes(cfg)
    flat_a = jax.tree.leaves_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_a) == len(flat_p)
    for (kp, vp), (ka, va) in zip(flat_p, flat_a):
        assert len(va) == vp.ndim, f"{kp}: axes {va} vs shape {vp.shape}"


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch, rng):
    """decode_step after prefill must reproduce the parallel logits.

    Run in fp32: this is a math-equivalence test (cache plumbing, ring
    buffers, recurrent state), so dtype noise would only mask bugs."""
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype=jnp.float32)
    if cfg.n_experts:
        # avoid capacity-drop nondeterminism between the two paths
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    seq = 12
    cfg, tokens, fe = _inputs(cfg, rng, batch=2, seq=seq + 1)
    params = registry.init(cfg, rng)

    logits_all = registry.forward(cfg, params, tokens, frontend_embeds=fe)
    logits_p, cache = registry.prefill(cfg, params, tokens[:, :seq],
                                       frontend_embeds=fe)
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_all[:, seq - 1]),
        rtol=1e-4, atol=1e-4)
    logits_d, cache = registry.decode_step(cfg, params, tokens[:, seq],
                                           cache)
    np.testing.assert_allclose(
        np.asarray(logits_d), np.asarray(logits_all[:, seq]),
        rtol=1e-4, atol=1e-4)
