"""Whole runs of each cell at small sizes on the CPU, without the look
for a chip: sound runs come out correct; the control, and each fault the
cell can have planted under the timed path, come out not correct."""
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import pytest
from conftest import load, small_decode, small_qwen3, small_ring

import control
import harness

PEAKS = load("peaks.json")["devices"]["TPU v5 lite"]
SEED = 2**33 + 5            # wider than 32 bits, as the driver's are


def run(cell, tmp_path, trace=False, seconds=1.0):
    config, traffic = ((small_qwen3(), small_decode())
                       if cell == "qwen3_4b.decode" else (small_ring(), None))
    return harness.measure(cell, SEED, seconds, trace, t_start=0.0,
                           config=config, traffic=traffic,
                           out_dir=pathlib.Path(tmp_path), peaks=PEAKS,
                           log=lambda _s: None)


CELLS = ["ring2048.run", "ring2048.sweep32", "qwen3_4b.decode"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(cell, trace, tmp_path):
    r = run(cell, tmp_path, trace=trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    e2e, layer = harness.metrics_of(harness.load_spec(),
                                    {"name": cell})
    if trace:
        assert "breakdown" in r and r["device"]["window_s"] > 0
    else:
        assert set(r["metrics"]) == {m["name"] for m in e2e}
        assert all(v["value"] > 0 for v in r["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, tmp_path):
    config, traffic = ((small_qwen3(), small_decode())
                       if cell == "qwen3_4b.decode" else (small_ring(), None))
    spec = harness.load_spec()
    limit = (traffic["limits"]["widest_logit_gap"] if traffic else 0)
    for seed in (1, 2, SEED):
        low, ctl, _ = control.readings(cell, seed, True, spec=spec,
                                       config=config, traffic=traffic,
                                       out_dir=pathlib.Path(tmp_path))
        assert low <= limit < ctl


def unchanged_state(tape, st, max_rounds, **_kw):
    return st


def altered_answer(fn):
    def inner(*args, **kw):
        report = fn(*args, **kw)
        return dataclasses.replace(report, vtime_ns=report.vtime_ns + 1)
    return inner


def half_batch(fn):
    def inner(tapes, states, max_rounds):
        half = jax.tree.leaves(tapes)[0].shape[0] // 2
        out = fn(jax.tree.map(lambda x: x[:half], tapes),
                 jax.tree.map(lambda x: x[:half], states), max_rounds)
        return jax.tree.map(lambda x: jnp.concatenate([x, x]), out)
    return inner


def test_fault_run_state_unchanged(tmp_path, monkeypatch):
    from repro.core import engine_jax
    monkeypatch.setattr(engine_jax, "run_vec_tape", unchanged_state)
    assert not run("ring2048.run", tmp_path)["correct"]


def test_fault_run_answer_altered(tmp_path, monkeypatch):
    from repro.sim import vectorized
    monkeypatch.setattr(vectorized, "_decompile",
                        altered_answer(vectorized._decompile))
    assert not run("ring2048.run", tmp_path)["correct"]


def test_fault_sweep_half_the_batch(tmp_path, monkeypatch):
    from repro.core import engine_jax
    monkeypatch.setattr(engine_jax, "run_vec_tape_batch",
                        half_batch(engine_jax.run_vec_tape_batch))
    assert not run("ring2048.sweep32", tmp_path)["correct"]


def test_fault_sweep_state_unchanged(tmp_path, monkeypatch):
    from repro.core import engine_jax
    monkeypatch.setattr(engine_jax, "run_vec_tape_batch",
                        lambda tapes, states, max_rounds: states)
    assert not run("ring2048.sweep32", tmp_path)["correct"]


def test_fault_sweep_answer_altered(tmp_path, monkeypatch):
    from repro.sim import vectorized
    monkeypatch.setattr(vectorized, "_decompile",
                        altered_answer(vectorized._decompile))
    assert not run("ring2048.sweep32", tmp_path)["correct"]


def test_fault_decode_token_altered(tmp_path, monkeypatch):
    from repro.models import registry
    step = registry.decode_step

    def shifted(cfg, params, token, cache):
        logits, cache = step(cfg, params, token, cache)
        return jnp.roll(logits, 1, axis=-1), cache
    monkeypatch.setattr(registry, "decode_step", shifted)
    assert not run("qwen3_4b.decode", tmp_path)["correct"]


def test_fault_decode_state_unchanged(tmp_path, monkeypatch):
    from repro.models import registry
    step = registry.decode_step

    def stale(cfg, params, token, cache):
        logits, _ = step(cfg, params, token, cache)
        return logits, cache
    monkeypatch.setattr(registry, "decode_step", stale)
    assert not run("qwen3_4b.decode", tmp_path)["correct"]
