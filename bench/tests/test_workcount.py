"""Work counts from shapes, checked by hand and against the program's own
parameter count."""
import dataclasses

import pytest
from conftest import load, small_qwen3

import workcount
from drivers import live_serve


def test_decoder_params_match_the_served_model():
    cfg = load("configs/qwen3_4b.json")
    p = workcount.decoder_params(cfg)
    total = p["layers"] * (p["layer_matmul"] + p["layer_norms"]) \
        + p["embed"] + p["final_norm"]
    assert p["published"] == total == 4_022_468_096     # tied, as published
    # the served model holds the tied head as a matrix of its own
    assert live_serve.model_config(cfg).n_params() == total + p["head"]


def test_decode_step_by_hand():
    cfg = small_qwen3()       # d 64, ff 128, 4 heads / 2 kv of 16, V 256
    flops, bytes_ = workcount.decode_step(cfg, batch=2, context=10)
    layer = 64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64 + 3 * 64 * 128
    assert flops == 2 * 2 * (2 * layer + 64 * 256) + 4 * 2 * 2 * 4 * 16 * 10
    weights = 2 * (layer + 2 * 64 + 2 * 16) + 64 * 256 + 64
    kv = 2 * 2 * 2 * 2 * 16 * 2
    assert bytes_ == 2 * weights + 2 * 64 * 2 + kv * 11


def test_full_size_decode_step_is_bound_by_bytes():
    cfg = load("configs/qwen3_4b.json")
    peaks = load("peaks.json")["devices"]["TPU v5 lite"]
    flops, bytes_ = workcount.decode_step(cfg, batch=8, context=1100)
    assert bytes_ / peaks["hbm_bytes_per_s"] \
        > 10 * flops / peaks["bf16_flops_per_s"]
    # the weights read once are most of it: about 8 GB of bfloat16
    assert 8.0e9 < bytes_ < 10.0e9


def test_kernel_counts():
    assert workcount.minskew_round(2048, 1) == (4096.0, 2048 + 4 * 2048
                                                + 2048 + 4 + 2048)
    assert workcount.hub_route_pass(8224) == (3 * 8224, 20 * 8224)


def test_least_time_is_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert workcount.least_s(1000, 5, peaks) == pytest.approx(10.0)
    assert workcount.least_s(100, 50, peaks) == pytest.approx(5.0)


def test_model_config_keeps_every_width():
    cfg = load("configs/qwen3_4b.json")
    mc = live_serve.model_config(cfg)
    assert (mc.d_model, mc.d_ff, mc.n_heads, mc.n_kv_heads, mc.hd,
            mc.vocab, mc.n_layers) == (2560, 9728, 32, 8, 128, 151936, 36)
    assert mc.qk_norm and mc.rope_theta == 1e6
    assert dataclasses.replace(mc).norm_eps == 1e-6
