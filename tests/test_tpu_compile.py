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
import os

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
