"""Test set-up: the benchmark's modules and the program on the path,
small configurations of each cell, and the JAX CPU backend."""
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def load(rel: str) -> dict:
    return json.loads((BENCH / rel).read_text())


def small_ring() -> dict:
    cfg = load("configs/ring2048.json")
    cfg.update(n_pods=2, chips_per_pod=8)
    return cfg


def small_qwen3() -> dict:
    cfg = load("configs/qwen3_4b.json")
    # initializer_range 0.125 gives each projection's outputs the scale
    # they have at full size (sqrt(width) x std near 1): at 0.02 and
    # width 64 the layers add next to nothing to the residual, the tied
    # head then puts the input token first by a wide margin, and no
    # precision shows in the served tokens
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=256, initializer_range=0.125)
    return cfg


def small_decode() -> dict:
    t = load("traffic/decode.json")
    # at this size, on the CPU, the program's widest gap reads 0 to
    # 0.026 and the float8 control's 0.115 to 0.44 (5 seeds each)
    t.update(batch=2, prompt_len=16, decode_steps=8, requests_per_call=2,
             check_requests=2, limits={"widest_logit_gap": 0.06})
    return t
