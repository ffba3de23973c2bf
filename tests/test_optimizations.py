"""Beyond-paper optimizations must be numerically exact vs baseline."""
import dataclasses
import pathlib
import subprocess
import sys


def test_tp_attention_exactness_subprocess():
    """tp_attention (TP-aligned GQA) == baseline forward, on a real 2x2
    mesh (needs 4 devices -> subprocess with its own XLA_FLAGS)."""
    prog = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.models import registry
from repro.launch.mesh import make_test_mesh
from repro.parallel import ctx as pctx

for arch in ("phi3_medium_14b", "qwen3_4b", "glm4_9b"):
    cfg = dataclasses.replace(configs.get_smoke(arch), dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    params = registry.init(cfg, key)
    base = registry.forward(cfg, params, tokens)
    mesh = make_test_mesh(data=2, model=2)
    cfg_tp = dataclasses.replace(cfg, tp_attention=True)
    with pctx.use_mesh(mesh):
        opt = jax.jit(lambda p, t: registry.forward(cfg_tp, p, t))(
            params, tokens)
    d = np.abs(np.asarray(base) - np.asarray(opt)).max()
    assert d < 1e-4, (arch, d)
print("TP_OK")
"""
    env = {**__import__("os").environ, "PYTHONPATH": "src"}
    res = subprocess.run([sys.executable, "-c", prog],
                         cwd=str(pathlib.Path(__file__).parent.parent),
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert "TP_OK" in res.stdout, res.stderr[-2000:]
