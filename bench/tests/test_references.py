"""The plain references against a second witness at small sizes: the
chip-ring reference against the program's host engine, the Qwen3
reference against the program's float32 forward pass."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import load, small_qwen3, small_ring

from drivers import live_serve, sim_fleet
from reference import chip_ring, qwen3


@pytest.mark.parametrize("pods,cpp,steps,stragglers", [
    (1, 4, 3, {}),
    (2, 8, 4, {"chip3": 1.75}),
    (3, 5, 2, {"chip0": 2.75, "chip11": 1.25}),
])
def test_chip_ring_matches_the_async_engine(pods, cpp, steps, stragglers):
    cfg = small_ring()
    cfg.update(n_pods=pods, chips_per_pod=cpp, n_steps=steps)
    drv = sim_fleet.Driver(cfg, load("traffic/run.json"), 0)
    report = drv._sim(stragglers).run(engine="async")
    ref = chip_ring.simulate(cfg, stragglers)
    for f in sim_fleet.FIELDS:
        assert getattr(report, f) == ref[f], f


def test_chip_ring_counts_at_full_size():
    ref = chip_ring.simulate(load("configs/ring2048.json"),
                             {"chip5": 2.0})
    assert ref["messages"] == 4 * (2048 + 8) == 8224
    assert ref["bytes"] == 4 * (2048 * 50_000_000 + 8 * 6_000_000)
    # messages, then compute, send and receive per chip and step and a
    # send and a receive more per pod leader and step
    assert chip_ring.events(load("configs/ring2048.json")) \
        == 8224 + 4 * (3 * 2048 + 2 * 8) == 32864


def f32(weights):
    return {k: v.astype(jnp.float32) for k, v in weights.items()}


@pytest.mark.parametrize("tied", [True, False])
def test_qwen3_reference_matches_the_program_forward(tied):
    from repro.models import registry
    cfg = small_qwen3()
    cfg["tie_word_embeddings"] = tied
    w = live_serve.make_weights(cfg, 7)
    mc = dataclasses.replace(live_serve.model_config(cfg),
                             dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, 256, (2, 24))
    with jax.default_matmul_precision("highest"):
        prog = registry.forward(mc, live_serve.program_params(f32(w)),
                                jnp.asarray(tokens))
    ref = qwen3.logits(cfg, w, tokens, 0)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(prog),
                               rtol=1e-4, atol=1e-4)
    tail = qwen3.logits(cfg, w, tokens, 20, vocab_block=100)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(ref)[:, 20:],
                               rtol=1e-5, atol=1e-5)


def test_qwen3_fp8_control_departs_from_the_reference():
    cfg = small_qwen3()
    w = live_serve.make_weights(cfg, 3)
    tokens = np.random.default_rng(1).integers(0, 256, (2, 24))
    ref = np.asarray(qwen3.logits(cfg, w, tokens, 0))
    low = np.asarray(qwen3.logits(cfg, w, tokens, 0, quant="fp8"))
    err = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5
