"""The simulator's kernels, compiled by the TPU compiler for a described
(not attached) v5e chip.

Interpret mode cannot show what the chip's compiler refuses: layouts it
cannot relayout, primitives Mosaic does not lower, blocks that do not
tile.  These tests compile the kernels of the main path at fleet shapes
and the jitted round loop that calls them, and check that the kernels
are in the program, and the live serve step's decode attention at the
benchmark's serving shapes.  Nothing runs, so they say nothing about
results or times (the oracle tests in ``test_kernels.py`` cover results).

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and every pytest
worker imports every test file.  Keep every such compile in this one
file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one (it warns and compiles
    again), so keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernels_in(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("n,s", [(2048, 1),       # the 2048-chip job
                                 (2148, 300)])    # several blocks each way
def test_minskew_compiles(one_chip, n, s):
    from repro.kernels.minskew import minskew
    compiled = jax.jit(minskew).lower(
        _spec(one_chip, (n,), jnp.int32), _spec(one_chip, (n,), jnp.int8),
        _spec(one_chip, (n, s), jnp.int8),
        _spec(one_chip, (s,), jnp.int32)).compile()
    assert _kernels_in(compiled) == 2          # minima + eligibility


def test_hub_route_compiles(one_chip):
    from repro.kernels.hub_route import hub_route
    m, links = 8224, 2056                      # the 2048-chip job's fan-out
    fn = jax.jit(lambda send, size, link, bw, lat, ser: hub_route(
        send, size, link, bw, lat, ser_ns=ser))
    vec = _spec(one_chip, (m,), jnp.int32)
    compiled = fn.lower(vec, vec, vec,
                        _spec(one_chip, (links,), jnp.float32),
                        _spec(one_chip, (links,), jnp.int32), vec).compile()
    assert _kernels_in(compiled) == 1


def test_round_loop_compiles_with_kernels(one_chip):
    """``run_vec_tape(pallas=True)`` on the tapes of the 2048-chip
    ``ChipRingTraining`` job that ``chip_smoke.py`` runs."""
    from repro.core import engine_jax as ej
    from repro.core.cluster import ClusterSpec, StepCost
    from repro.sim import ChipRingTraining, Simulation, Topology
    from repro.sim.vectorized import compile_simulation
    wl = ChipRingTraining(ClusterSpec(n_pods=8, chips_per_pod=256),
                          StepCost(compute_ns=5_000_000,
                                   ici_bytes=50_000_000,
                                   dcn_bytes=6_000_000), n_steps=4)
    comp = compile_simulation(Simulation(Topology.single_host(n_cpus=64),
                                         wl))
    assert comp.tape.op_kind.shape == (2048, 20)
    st0 = ej.init_vec_sim_state(comp.tape, comp.n_channels)

    def spec(x):
        x = jnp.asarray(x)
        return _spec(one_chip, x.shape, x.dtype)

    compiled = ej.run_vec_tape.lower(
        jax.tree.map(spec, comp.tape), jax.tree.map(spec, st0),
        spec(jnp.int32(comp.max_rounds)), pallas=True).compile()
    assert _kernels_in(compiled) == 2


def test_decode_attention_reads_the_cache_in_place(one_chip):
    """Grouped-query decode at the qwen3-4b serving shapes (batch 8,
    32 query / 8 KV heads of 128, cache 1281) contracts each KV head
    with its query group: no copy of the cache repeated to 32 heads, and
    the products are matrix products, not elementwise multiply-reduces."""
    from repro.models.attention import decode_attention
    cache = _spec(one_chip, (8, 1281, 8, 128), jnp.bfloat16)
    compiled = jax.jit(decode_attention).lower(
        _spec(one_chip, (8, 1, 32, 128), jnp.bfloat16), cache, cache,
        _spec(one_chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    for repeated in ("[8,1281,8,4,128]", "[8,1281,32,128]"):
        assert repeated not in text
    assert "multiply_reduce_fusion" not in text


def test_latent_decode_step_compiles_with_named_kernels(one_chip,
                                                        monkeypatch):
    """Moonlight-16B-A3B's decode step with one EP8 chip's 8 of 64
    experts, at the ``moonlight_16b_a3b.decode64`` cell's shapes (batch
    64, cache 1024 + 257 rounded up to 1408): the latent attention and
    the shared experts are the named kernels, the held experts the
    compiler's ``ragged-dot``.  The caches are read in place:
    their only copies are of the step's undonated input, one each, and
    the step needs no other device memory."""
    import dataclasses

    from repro import configs
    from repro.models import registry
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(configs.get("moonshot_v1_16b_a3b"),
                              n_held_experts=8, remat=False)

    def spec(s):
        return _spec(one_chip, s.shape, s.dtype)
    params = jax.tree.map(spec, registry.param_specs(cfg))
    cache = jax.tree.map(spec, registry.cache_specs(cfg, 64, 1281))
    assert cache["ckv"].shape == (27, 64, 1408, 512)
    compiled = jax.jit(lambda p, t, c: registry.decode_step(cfg, p, t, c)
                       ).lower(params, _spec(one_chip, (64,), jnp.int32),
                               cache).compile()
    text = compiled.as_text()
    for name in ("%mla_decode_attention", "%moe_shared", "%ragged-dot"):
        assert name in text, name
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and ("[27,64,1408,512]" in ln
                                     or "[27,64,64,1408]" in ln)]
    assert len(copies) == 2, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 2**28


def _shapes(text: str):
    """Instruction name -> dimensions, over every computation of the
    module, fused ones included (names are unique across the module)."""
    return {m.group(1): tuple(int(d) for d in m.group(2).split(",") if d)
            for m in re.finditer(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text)}


def _updates(text: str):
    """The shapes of the updates of every ``dynamic-update-slice``."""
    shapes = _shapes(text)
    return [shapes[m.group(1)] for m in re.finditer(
        r"dynamic-update-slice\(%[\w.\-]+, (%[\w.\-]+)", text)]


@pytest.mark.parametrize("arch,batch", [("qwen3_4b", 8),
                                        ("moonshot_v1_16b_a3b", 64)])
def test_served_decode_step_updates_the_cache_in_place(one_chip,
                                                       monkeypatch, arch,
                                                       batch):
    """``BatchServer._decode`` itself at the decode cells' shapes
    (``qwen3_4b.decode``: batch 8; ``moonlight_16b_a3b.decode64``: batch
    64, one EP8 chip's 8 experts; cache 1024 + 257 positions): the
    donated cache is aliased to the step's output whole, no whole cache
    is copied, the step needs under 256 MiB besides, and each dense
    layer writes only its new row, never a whole layer's slice, and is
    read in place: no copy of a layer's K or V anywhere in the program,
    inside a fusion or not (a relayout of each layer for the attention's
    products costs more than the whole cache's copy it replaces)."""
    import dataclasses

    from repro import configs
    from repro.models import registry
    from repro.serve.loop import BatchServer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(configs.get(arch), remat=False)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, n_held_experts=8)

    def spec(s):
        return _spec(one_chip, s.shape, s.dtype)
    params = jax.tree.map(spec, registry.param_specs(cfg))
    cache = jax.tree.map(spec, registry.cache_specs(cfg, batch, 1281))
    compiled = BatchServer(cfg, None)._decode.lower(
        params, _spec(one_chip, (batch,), jnp.int32), cache).compile()
    text = compiled.as_text()
    leaves = jax.tree.leaves(cache)
    whole = {"[" + ",".join(map(str, a.shape)) + "]" for a in leaves
             if a.ndim}
    copies = [ln for ln in text.splitlines()
              if " copy(" in ln and any(w in ln for w in whole)]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize
                                          for a in leaves)
    assert mem.temp_size_in_bytes < 2**28
    if not cfg.kv_lora_rank:
        assert cache["k"].shape == (36, 8, 8, 1296, 128)
        layer = math.prod(cache["k"].shape[1:])
        updates = _updates(text)
        assert updates
        assert max(map(math.prod, updates)) < layer
        shapes = _shapes(text)
        big = [name for name in re.findall(r"(%[\w.\-]+) = \S+ copy\(", text)
               if math.prod(shapes[name]) >= layer]
        assert not big, big


@pytest.mark.parametrize("t", [64, 8192])
def test_shared_expert_kernel_compiles(one_chip, t):
    """The shared experts' kernel at a decode step's 64 tokens and a
    prefill chunk's 8192, Moonlight's widths."""
    from repro.kernels.moe import moe_shared
    bf = jnp.bfloat16
    assert _kernels_in(jax.jit(moe_shared).lower(
        _spec(one_chip, (t, 2048), bf), _spec(one_chip, (2048, 2816), bf),
        _spec(one_chip, (2048, 2816), bf),
        _spec(one_chip, (2816, 2048), bf)).compile()) == 1
