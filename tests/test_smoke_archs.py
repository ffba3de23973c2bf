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


@pytest.mark.parametrize("arch", ["qwen3_4b", "olmoe_1b_7b",
                                  "moonshot_v1_16b_a3b"])
def test_long_prefill_runs_in_passes_and_matches_forward(arch, rng):
    """Past ``PREFILL_CHUNK`` tokens the prefill runs each feed-forward
    block in passes over spans of the sequence, the whole batch in each
    (here 64 sequences of 129 positions: two spans of 65, the last padded
    by one).  Its logits, and a decode step from its cache, still give
    ``forward``'s (float32, 1e-4, as above)."""
    from repro.models import transformer

    cfg = dataclasses.replace(configs.get_smoke(arch), dtype=jnp.float32)
    batch, seq = 64, 129
    assert batch * seq > transformer.PREFILL_CHUNK >= batch * (seq // 2 + 1)
    tokens = jax.random.randint(rng, (batch, seq + 1), 0, cfg.vocab)
    params = registry.init(cfg, rng)

    logits_all = registry.forward(cfg, params, tokens)
    logits_p, cache = registry.prefill(cfg, params, tokens[:, :seq])
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_all[:, seq - 1]),
        rtol=1e-4, atol=1e-4)
    logits_d, _ = registry.decode_step(cfg, params, tokens[:, seq], cache)
    np.testing.assert_allclose(
        np.asarray(logits_d), np.asarray(logits_all[:, seq]),
        rtol=1e-4, atol=1e-4)


def test_long_prefill_compiles_on_a_mesh():
    """On a (data 2, model 2) mesh a prefill past ``PREFILL_CHUNK``
    tokens per device (here two passes of 128 positions, against eight
    without the mesh) keeps the batch whole in every pass, so the
    expert-parallel layer still divides it over ``data``: the olmoe
    prefill compiles with the shardings the dry run gives it, and its
    logits match the prefill's without a mesh (capacity 8: nothing
    dropped).  A decode step from that cache gives the logits it gives
    without the mesh, and gathers no cache onto a device: the cache is
    not pinned to a layout there, which the partitioner could only do on
    the whole cache.  Needs four devices, so it runs in a subprocess
    with its own XLA_FLAGS."""
    import os
    import pathlib
    import subprocess
    import sys

    prog = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, math, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro import configs
from repro.launch.mesh import make_test_mesh
from repro.models import registry, transformer
from repro.parallel import ctx as pctx
from repro.parallel import sharding as shd
from repro.serve.step import (build_decode_step, build_prefill_step,
                              cache_shardings, serve_rules)

cfg = dataclasses.replace(configs.get_smoke("olmoe_1b_7b"),
                          dtype=jnp.float32, capacity_factor=8.0)
batch, seq = 256, 256
assert batch * seq > 4 * transformer.PREFILL_CHUNK
params = registry.init(cfg, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                            cfg.vocab)
token = tokens[:, -1]
want, cache = jax.jit(build_prefill_step(cfg))(params, tokens)
want_d, _ = jax.jit(build_decode_step(cfg))(params, token, cache)
mesh = make_test_mesh(data=2, model=2)
with pctx.use_mesh(mesh):
    rules = serve_rules(cfg, mesh, batch)
    p_sh = shd.shardings_from_axes(registry.logical_axes(cfg), mesh, rules,
                                   registry.param_specs(cfg))
    logits_sh = NamedSharding(mesh, shd.spec_from_axes(
        ("batch", "vocab"), mesh, rules, (batch, cfg.vocab)))
    c_sh = cache_shardings(cfg, mesh, batch, seq + 64, rules)
    fn = jax.jit(build_prefill_step(cfg),
                 in_shardings=(p_sh, shd.batch_sharding(mesh, 2)),
                 out_shardings=(logits_sh, c_sh))
    got, cache = fn(params, tokens)
    tok_sh = NamedSharding(mesh, shd.spec_from_axes(
        ("batch",), mesh, rules, (batch,)))
    step = jax.jit(build_decode_step(cfg),
                   in_shardings=(p_sh, tok_sh, c_sh),
                   out_shardings=(logits_sh, c_sh))
    text = step.lower(params, token, cache).compile().as_text()
    got_d, _ = step(params, token, cache)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d),
                           rtol=1e-4, atol=1e-4)
half_cache = cache["k"].size // 2
gathered = [math.prod(int(d) for d in m.group(1).split(",") if d)
            for m in re.finditer(r"= \w+\[([\d,]*)\][^=]* all-gather\(",
                                 text)]
assert max(gathered, default=0) < half_cache, (gathered, half_cache)
print("MESH_PREFILL_OK")
"""
    root = pathlib.Path(__file__).resolve().parents[1]
    env = {**os.environ, "PYTHONPATH": str(root / "src"),
           "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", prog], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert "MESH_PREFILL_OK" in res.stdout, res.stderr[-2000:]
