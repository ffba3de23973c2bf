"""The ``moonlight_16b_a3b.decode64`` cell at small sizes on the CPU:
work counts against hand counts, each reader the cell reports, and
whole runs of the cell served in bfloat16 as on the chip: sound runs
come out correct; the float8 control, and each fault planted under the
timed path, come out not correct."""
import pathlib
import types

import jax
import jax.numpy as jnp
import pytest
from conftest import load

import control
import harness
import tracereduce
import workcount
import workcount_mla
from drivers import live_serve_mla

CELL = "moonlight_16b_a3b.decode64"
PEAKS = load("peaks.json")["devices"]["TPU v5 lite"]
SEED = 2**33 + 5            # wider than 32 bits, as the driver's are
#: a window that holds one call, so a planted fault is judged on the
#: same wave whatever the machine's speed
ONE_CALL = 1e-3


def small_moonlight() -> dict:
    """3 layers (1 dense, 2 expert) at small widths; the routing as
    published (64 experts, top-6) with one EP8 chip's 8 held.
    initializer_range 0.125 gives each projection's outputs the scale
    they have at full size (sqrt(width) x std near 1)."""
    cfg = load("configs/moonlight_16b_a3b.json")
    cfg.update(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, kv_lora_rank=32, v_head_dim=16,
               intermediate_size=128, moe_intermediate_size=32,
               num_hidden_layers=3, vocab_size=256, initializer_range=0.125)
    return cfg


def small_decode64() -> dict:
    """One wave of 8 requests, all 8 judged.  The check at this size
    (CPU, 8 seeds, one call each): at positions where no held expert's
    biased score lies within 0.01 of the chosen experts' boundary in
    either expert layer (70 to 80 % of them), the program reads 0.0095
    to 0.036, the float8 control 0.39 to 1.62 and a zeroed held expert
    0.11 to 0.82; the limit 0.08 leaves 2.2 times the program's largest.
    Over all positions the program's near-ties read up to 0.31 and
    overlap the control."""
    t = load("traffic/decode64.json")
    t.update(batch=8, prompt_len=16, decode_steps=32, requests_per_call=8,
             check_requests=8, tie_margin=0.01,
             limits={live_serve_mla.GAP: 0.08})
    return t


def run(tmp_path, trace=False, seconds=1.0):
    return harness.measure(CELL, SEED, seconds, trace, t_start=0.0,
                           config=small_moonlight(),
                           traffic=small_decode64(),
                           out_dir=pathlib.Path(tmp_path), peaks=PEAKS,
                           log=lambda _s: None)


# -- work counts ----------------------------------------------------------------


def test_parameter_counts():
    cfg = load("configs/moonlight_16b_a3b.json")
    p = workcount_mla.params(cfg)
    assert p["attn"] == 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 \
        + 16 * 128 * 2048 == 13_762_560
    assert p["published"] == 15_960_110_208         # the published 15.96 B
    assert p["held"] == 3_364_615_296               # 8 of 64 experts held
    assert live_serve_mla.model_config(cfg).n_params() == p["held"]


def test_decode_step_by_hand():
    cfg = small_moonlight()     # d 64, 4 heads, nope 16, rope 8, R 32, v 16
    flops, bytes_ = workcount_mla.decode_step(cfg, batch=2, context=10)
    attn = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64
    dense, expert, shared = 3 * 64 * 128, 3 * 64 * 32, 3 * 64 * 64
    router = 64 * 64 + 64
    per_token = 3 * attn + dense + 2 * (router + shared + expert * 6 * 8 / 64) \
        + 64 * 256
    latent = 2 * 3 * 2 * 4 * (2 * 32 + 8) * 10
    assert flops == pytest.approx(2 * 2 * per_token + latent)
    held = 3 * (attn + 2 * 64 + 32) + dense \
        + 2 * (8 * expert + router + shared) + 2 * 64 * 256 + 64
    weights = 2 * (held - 64 * 256 - 2 * router) + 4 * 2 * router
    cache = 3 * 2 * (32 + 8) * 2 * 11
    assert bytes_ == weights + 2 * 64 * 2 + cache


def test_kernel_work_by_hand():
    cfg = small_moonlight()
    flops, bytes_ = workcount_mla.mla_attention(cfg, batch=2, context=10)
    assert flops == 2 * 3 * 2 * 4 * (32 + 8 + 32) * 10
    assert bytes_ == 3 * 2 * (10 * 40 * 2 + 4 * 40 * 2 + 4 * 32 * 2)
    flops, bytes_ = workcount_mla.experts(cfg, batch=2)
    assert flops == 2 * 2 * 2 * (3 * 64 * 32 * 6 * 8 / 64 + 3 * 64 * 64)
    assert bytes_ == 2 * (8 * 3 * 64 * 32 + 3 * 64 * 64) * 2


def test_full_size_decode_step_is_bound_by_bytes():
    """The step's least time: 6.06 GB of held weights and 2.29 GB of
    latent cache at context 1152, about 10.2 ms on a v5e."""
    cfg = load("configs/moonlight_16b_a3b.json")
    flops, bytes_ = workcount_mla.decode_step(cfg, batch=64, context=1152)
    assert bytes_ / PEAKS["hbm_bytes_per_s"] \
        > 5 * flops / PEAKS["bf16_flops_per_s"]
    assert workcount.least_s(flops, bytes_, PEAKS) \
        == pytest.approx(10.2e-3, rel=0.02)
    rows = 27 * 64 * 1152 * workcount_mla.latent_row_bytes(cfg)
    assert rows == pytest.approx(2.29e9, rel=0.01)
    _, kernel = workcount_mla.mla_attention(cfg, 64, 1152)
    assert kernel == rows + 27 * 64 * 16 * (576 + 512) * 2
    _, experts = workcount_mla.experts(cfg, 64)
    assert experts == pytest.approx(4.50e9, rel=0.01)


# -- readers ----------------------------------------------------------------------


def op(name: str, shape: str = "bf16[64,2048]") -> str:
    return f"%{name} = {shape}{{1,0}} custom-call(bf16[64,2048] %x)"


def synthetic(ops, decode_spans):
    """A trace of one chip: ``ops`` (start, end, hlo name[, result
    type]) in a window from 0 to 100 us with ``bench.decode`` spans at
    ``decode_spans``."""
    spans = [(0.0, 100e3, "bench.window")] + [
        (s, e, "bench.decode") for s, e in decode_spans]
    return tracereduce.Trace({0: [(s, e, op(*n)) for s, e, *n in ops]},
                             {0: []}, spans)


def reader_ctx(trace, steps):
    return types.SimpleNamespace(
        trace=trace, counts={"decode_steps": steps, "batch": 64},
        config=load("configs/moonlight_16b_a3b.json"),
        traffic=load("traffic/decode64.json"), peaks=PEAKS)


def test_mla_decode_roofline_reads_the_kernel_in_decode_steps():
    trace = synthetic([(1e3, 3e3, "mla_decode_attention.4"),
                       (4e3, 5e3, "mla_decode_attention"),
                       (20e3, 30e3, "mla_decode_attention.4"),  # no step
                       (5e3, 6e3, "fusion.12")],
                      [(0.0, 10e3)])
    steps = [(1100, 9e3)]
    got = harness.load_reader("mla_decode_roofline")(reader_ctx(trace,
                                                                steps))
    need = workcount.least_s(*workcount_mla.mla_attention(
        load("configs/moonlight_16b_a3b.json"), 64, 1100), PEAKS)
    assert got == pytest.approx(100.0 * need / 3e-6)
    empty = synthetic([(1e3, 3e3, "fusion.1")], [(0.0, 10e3)])
    assert harness.load_reader("mla_decode_roofline")(
        reader_ctx(empty, steps)) is None


def test_moe_experts_roofline_reads_grouped_matmuls_and_shared():
    """The grouped matmuls, the shared kernel and the staging of a
    layer's held (8 x 2048 x 1408) and shared (2048 x 2816) weights."""
    trace = synthetic([(1e3, 2e3, "ragged-dot-none.1"),
                       (2e3, 4e3, "ragged-dot-metadata"),
                       (4e3, 5e3, "moe_shared.2"),
                       (5e3, 9e3, "fusion.7"),                  # routing
                       (51e3, 53e3, "dynamic-slice_bitcast_fusion.16",
                        "bf16[8,2048,1408]"),
                       (53e3, 54e3, "dynamic-slice_bitcast_fusion.3",
                        "bf16[2048,2816]"),
                       (54e3, 56e3, "dynamic-slice_bitcast_fusion.4",
                        "bf16[2048,11264]"),                    # dense
                       (30e3, 40e3, "ragged-dot-none.1")],      # no step
                      [(0.0, 10e3), (50e3, 60e3)])
    steps = [(1100, 9e3), (1101, 9e3)]
    got = harness.load_reader("moe_experts_roofline")(reader_ctx(trace,
                                                                 steps))
    need = workcount.least_s(*workcount_mla.experts(
        load("configs/moonlight_16b_a3b.json"), 64), PEAKS)
    assert got == pytest.approx(100.0 * 2 * need / 7e-6)


def test_decode_mfu_and_idle_read_the_cell():
    trace = synthetic([(0.0, 25e3, "fusion.1"), (50e3, 100e3, "fusion.2")],
                      [(0.0, 10e3)])
    steps = [(1025, 20e6), (1026, 21e6)]
    ctx = reader_ctx(trace, steps)
    cfg = ctx.config
    need = sum(workcount.least_s(*workcount_mla.decode_step(cfg, 64, c),
                                 PEAKS) for c, _ in steps)
    assert harness.load_reader("decode_mfu.decode64")(ctx) \
        == pytest.approx(100.0 * need / 41e-3)
    assert harness.load_reader("device_idle.decode")(ctx) \
        == pytest.approx(25.0)
    assert harness.load_reader("decode_mfu.decode64")(
        reader_ctx(trace, [])) is None


# -- whole runs ---------------------------------------------------------------------


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(trace, tmp_path):
    r = run(tmp_path, trace=trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    e2e, layer = harness.metrics_of(harness.load_spec(), {"name": CELL})
    if trace:
        # the program-span readers read on the CPU; the device readers
        # find no chip plane and stay silent
        assert {"decode_mfu.decode64", "compiles_per_call.decode",
                "charged_idle_ms.decode"} <= set(r["metrics"])
        assert r["metrics"]["compiles_per_call.decode"]["value"] == 0
    else:
        assert set(r["metrics"]) == {m["name"] for m in e2e} \
            == {"live_decode_step_p95_ms", "setup_s"}


def test_control_is_not_correct(tmp_path):
    spec = harness.load_spec()
    limit = small_decode64()["limits"][live_serve_mla.GAP]
    for seed in (1, 2, SEED):
        low, ctl, _ = control.readings(CELL, seed, True, spec=spec,
                                       config=small_moonlight(),
                                       traffic=small_decode64(),
                                       out_dir=pathlib.Path(tmp_path))
        assert low <= limit < ctl


def test_fault_served_token_altered(tmp_path, monkeypatch):
    from repro.models import registry
    step = registry.decode_step

    def shifted(cfg, params, token, cache):
        logits, cache = step(cfg, params, token, cache)
        return jnp.roll(logits, 1, axis=-1), cache
    monkeypatch.setattr(registry, "decode_step", shifted)
    assert not run(tmp_path, seconds=ONE_CALL)["correct"]


def test_fault_routed_slot_dropped(tmp_path, monkeypatch):
    """The first slot on a held expert, of every dispatch, is dropped."""
    from repro.models import moe
    held = moe.held_experts

    def drop_one(cfg, p, xt, ids, w):
        local = ids - cfg.first_held_expert
        on_held = ((local >= 0) & (local < cfg.held_experts)).reshape(-1)
        first = jnp.argmax(on_held)
        w = w.reshape(-1).at[first].set(0.0).reshape(w.shape)
        return held(cfg, p, xt, ids, w)
    monkeypatch.setattr(moe, "held_experts", drop_one)
    assert not run(tmp_path, seconds=ONE_CALL)["correct"]


def test_fault_held_expert_zeroed(tmp_path, monkeypatch):
    from repro.models import moe
    held = moe.held_experts

    def zero_first(cfg, p, xt, ids, w):
        p = dict(p, w_down=p["w_down"].at[0].set(0))
        return held(cfg, p, xt, ids, w)
    monkeypatch.setattr(moe, "held_experts", zero_first)
    assert not run(tmp_path, seconds=ONE_CALL)["correct"]


def test_fault_stale_latent_cache(tmp_path, monkeypatch):
    from repro.models import registry
    step = registry.decode_step

    def stale(cfg, params, token, cache):
        logits, _ = step(cfg, params, token, cache)
        return logits, cache
    monkeypatch.setattr(registry, "decode_step", stale)
    assert not run(tmp_path, seconds=ONE_CALL)["correct"]
