"""Vectorized-engine conformance: the two-tier contract of
``Simulation.run(engine="vectorized")`` (see tests/engine_harness.py),
the ``UnsupportedByEngine`` surface, the int32 tick-range guard, and
the ``Simulation.sweep`` vmap batch and its shared structure.
"""
import dataclasses
import math

import numpy as np
import pytest

from engine_harness import (assert_vectorized_exact,
                            assert_vectorized_tolerance,
                            assert_engines_agree, run_engine)
from repro.core.cluster import ClusterSpec, StepCost
from repro.core.engine_jax import INF_TICKS
from repro.sim import (BitFlip, ChipRingTraining, ClockSkew, DegradeLink,
                       FailHost, FailTask, Interference, JoinHost,
                       ModeledServe, RackRing, Scenario, Simulation,
                       Straggler, TickRangeError, Topology,
                       UnsupportedByEngine)
from repro.sim import vectorized


def rack_sim(sc=None, *, n_iters=12, skew=100_000, compute=5_000):
    def make():
        wl = RackRing(n_racks=2, hosts_per_rack=2, n_iters=n_iters,
                      compute_ns=compute, msg_bytes=4096, cross_every=4,
                      skew_bound_ns=skew)
        return Simulation(Topology.racks(2, 2), wl, sc)
    return make


def chip_sim(sc=None):
    def make():
        wl = ChipRingTraining(
            ClusterSpec(n_pods=2, chips_per_pod=4),
            StepCost(compute_ns=50_000, ici_bytes=8192,
                     dcn_bytes=65536), n_steps=5,
            skew_bound_ns=1_000_000)
        return Simulation(
            Topology.full_mesh(2, link=Topology().default_host_link),
            wl, sc, placement={f"chip{i}": i // 4 for i in range(8)})
    return make


# --------------------------------------------------------------------------
# exact tier
# --------------------------------------------------------------------------

class TestExactTier:
    def test_rack_baseline_all_engines(self):
        """Vectorized joins the full cross-engine bar (barrier, async,
        dist) on a clean multi-host scenario."""
        make = rack_sim()
        assert_engines_agree(make)
        assert_vectorized_exact(make, ref_engine="async")

    def test_single_host_vs_single_engine(self):
        def make():
            wl = RackRing(n_racks=1, hosts_per_rack=1, n_iters=8,
                          compute_ns=3_000)
            return Simulation(Topology.single_host(), wl)
        assert_vectorized_exact(make, ref_engine="single")
        assert_vectorized_exact(make, ref_engine="async")

    def test_chipring_two_pods(self):
        assert_vectorized_exact(chip_sim(), ref_engine="async")
        assert_vectorized_exact(chip_sim(), ref_engine="barrier")

    def test_straggler(self):
        sc = Scenario("s", (Straggler("w1", 2.5), Straggler("w1", 1.5)))
        assert_vectorized_exact(rack_sim(sc))

    def test_fail_host_deadlocks_identically(self):
        sc = Scenario("f", (FailHost(1, at_vtime=160_000),))
        reports = assert_vectorized_exact(rack_sim(sc))
        assert reports["vectorized"].status == "deadlock"

    def test_fail_at_compute(self):
        sc = Scenario("fc", (FailTask("w2", at_compute=3),))
        reports = assert_vectorized_exact(rack_sim(sc))
        assert reports["vectorized"].tasks["w2"]["state"] == "done"

    def test_degrade_link_hosts(self):
        sc = Scenario("d", (DegradeLink(hosts=(0, 2), extra_ns=7_000,
                                        from_vtime=50_000),))
        assert_vectorized_exact(rack_sim(sc))

    def test_degrade_link_fabric(self):
        sc = Scenario("d", (DegradeLink(fabric="hub",
                                        latency_factor=3.0),))
        assert_vectorized_exact(rack_sim(sc))

    def test_interference_load(self):
        def make():
            wl = RackRing(n_racks=2, hosts_per_rack=1, n_iters=6,
                          compute_ns=4_000, cross_every=2)
            sc = Scenario("i", (Interference(host=1, bursts=5,
                                             burst_ns=2_000),))
            return Simulation(
                Topology.full_mesh(2,
                                   link=Topology().default_host_link),
                wl, sc)
        assert_vectorized_exact(make)

    def test_on_deadlock_raise(self):
        from repro.core.scheduler import DeadlockError
        sc = Scenario("f", (FailHost(1, at_vtime=160_000),))
        with pytest.raises(DeadlockError):
            rack_sim(sc)().run(engine="vectorized",
                               on_deadlock="raise")

    def test_pallas_interpret_matches_jnp(self):
        """The Pallas minskew/hub_route path (interpret mode on CPU)
        is bit-identical to the jnp fallback."""
        make = rack_sim(Scenario("s", (Straggler("w0", 1.75),)))
        off = make().run(engine="vectorized", pallas="off",
                         verify=True)
        interp = make().run(engine="vectorized", pallas="interpret",
                            verify=True)
        d_off, d_int = off.to_dict(), interp.to_dict()
        d_off["wall_s"] = d_int["wall_s"] = 0.0
        assert d_off == d_int


# --------------------------------------------------------------------------
# tolerance tier
# --------------------------------------------------------------------------

class TestToleranceTier:
    def test_quantized_rack(self):
        reports = assert_vectorized_tolerance(
            rack_sim(), tick_ns=100, vtime_tol_ns=20_000)
        assert reports["vectorized"].tier == "tolerance"
        assert reports["vectorized"].tick_ns == 100

    def test_quantized_with_faults(self):
        sc = Scenario("mix", (Straggler("w1", 2.5),
                              FailHost(3, at_vtime=200_000)))
        assert_vectorized_tolerance(rack_sim(sc), tick_ns=100,
                                    vtime_tol_ns=20_000)

    def test_divisible_explicit_tick_stays_exact(self):
        """An explicit tick that divides every ns quantity (computes,
        send overhead, serialization, latency) is still the exact tier
        — quantization is lossless."""
        def make():
            # local_link moves 80 bytes/ns, so 40000 bytes serialize in
            # exactly 500 ns; every quantity is a multiple of 500
            wl = RackRing(n_racks=1, hosts_per_rack=1, n_iters=8,
                          compute_ns=3_000, msg_bytes=40_000)
            return Simulation(Topology.single_host(), wl)
        rep = make().run(engine="vectorized", tick_ns=500,
                         verify=True)
        assert rep.tier == "exact"
        ref = make().run(engine="single")
        assert rep.vtime_ns == ref.vtime_ns
        assert rep.tasks == ref.tasks


# --------------------------------------------------------------------------
# UnsupportedByEngine surface
# --------------------------------------------------------------------------

class TestUnsupported:
    def test_live_program(self):
        wl = RackRing(n_racks=1, hosts_per_rack=2, n_iters=4,
                      live=True)
        sim = Simulation(Topology.full_mesh(
            2, link=Topology().default_host_link), wl)
        with pytest.raises(UnsupportedByEngine, match="live"):
            sim.run(engine="vectorized")

    def test_cells(self):
        topo = Topology.single_host()
        topo.cell("hot", ways=4)
        wl = RackRing(n_racks=1, hosts_per_rack=1, n_iters=4,
                      live=True, cells={"w0": "hot"})
        with pytest.raises(UnsupportedByEngine):
            Simulation(topo, wl).run(engine="vectorized")

    def test_auto_cells_colocation(self):
        wl = RackRing(n_racks=1, hosts_per_rack=2, n_iters=4)
        sim = Simulation(Topology.single_host(), wl, cells="auto")
        with pytest.raises(UnsupportedByEngine, match="cell"):
            sim.run(engine="vectorized")

    def test_cpu_resource(self):
        wl = RackRing(n_racks=1, hosts_per_rack=1, n_iters=4)
        sim = Simulation(Topology.single_host(), wl, cpu_resource=True)
        with pytest.raises(UnsupportedByEngine, match="cpu_resource"):
            sim.run(engine="vectorized")

    def test_workload_without_lowering(self):
        """ModeledServe has no vec_ops: its server receives from many
        clients, so receive matching is schedule-dependent."""
        sim = Simulation(Topology.single_host(),
                         ModeledServe(n_clients=2, n_requests=3))
        with pytest.raises(UnsupportedByEngine, match="vec_ops"):
            sim.run(engine="vectorized")

    def test_reference_engines_unaffected(self):
        """Scenarios the vectorized engine rejects still run (and still
        agree) on the reference engines."""
        def make():
            wl = RackRing(n_racks=1, hosts_per_rack=1, n_iters=4)
            return Simulation(Topology.single_host(), wl,
                              cpu_resource=True)
        assert_engines_agree(make)


# --------------------------------------------------------------------------
# int32 tick-range guard (no silent overflow)
# --------------------------------------------------------------------------

class TestTickRange:
    def _big_ring(self):
        # two workers so the ring actually messages: the 500/51 ns
        # message quantities force the auto tick to 1, and the 2**30 ns
        # computes then blow the 2**30-tick horizon bound
        wl = RackRing(n_racks=1, hosts_per_rack=2, n_iters=2,
                      compute_ns=INF_TICKS)
        return Simulation(Topology.single_host(), wl)

    def test_horizon_over_range_raises(self):
        with pytest.raises(TickRangeError, match="tick_ns"):
            self._big_ring().run(engine="vectorized")

    def test_coarser_tick_recovers(self):
        """The error message's remedy works: a coarser explicit tick
        brings the same scenario back in range (tolerance tier, since
        the 500 ns send overhead does not divide 1024)."""
        rep = self._big_ring().run(engine="vectorized", tick_ns=1024)
        assert rep.status == "ok"
        assert rep.tier == "tolerance"
        # the reference engines run on python ints — no range limit
        ref = self._big_ring().run(engine="single")
        assert abs(rep.vtime_ns - ref.vtime_ns) <= 1024 * 16

    def test_boundary_is_tight(self):
        """A horizon bound just under 2**30 ticks (explicit tick_ns=1,
        so no gcd compression) compiles and runs; the guard is not
        spuriously conservative near the boundary."""
        def make():
            wl = RackRing(n_racks=1, hosts_per_rack=1, n_iters=1,
                          compute_ns=INF_TICKS - 2048)
            return Simulation(Topology.single_host(), wl)
        rep = make().run(engine="vectorized", tick_ns=1, verify=True)
        assert rep.status == "ok"
        assert rep.tier == "exact"
        assert rep.vtime_ns == make().run(engine="single").vtime_ns
        assert rep.vtime_ns == INF_TICKS - 2048

    def test_vecstate_create_boundary(self):
        """VecState.create (the raw array engine) validates
        durations*steps against the int32 range with an explicit
        error, instead of silently wrapping."""
        from repro.core.engine_jax import VecState
        n = 4
        member = np.ones((n, 1), bool)
        skews = np.array([1000])
        ok = VecState.create(n, 1, np.full(n, 2**20), np.full(n, 2**9),
                             member, skews)
        assert ok.vtime.shape == (n,)
        with pytest.raises(TickRangeError, match="task"):
            VecState.create(n, 1, np.full(n, 2**21), np.full(n, 2**9),
                            member, skews)

    def test_vecstate_create_rejects_negative(self):
        from repro.core.engine_jax import VecState
        with pytest.raises(ValueError):
            VecState.create(2, 1, np.array([-1, 5]), np.array([3, 3]),
                            np.ones((2, 1), bool), np.array([10]))


# --------------------------------------------------------------------------
# batched sweep
# --------------------------------------------------------------------------

class TestSweep:
    # stragglers change tape values, never tape shapes, so these share
    # scenario structure with the baseline
    AXIS = [Scenario("base"),
            Scenario("s1", (Straggler("w1", 2.0),)),
            Scenario("s2", (Straggler("w3", 3.0),
                            Straggler("w0", 1.5)))]

    def test_sweep_matches_solo_and_reference(self):
        res = rack_sim()().sweep(self.AXIS)
        assert res.tier == "exact"
        assert len(res.reports) == len(self.AXIS)
        assert res.configs_per_s > 0
        for sc, rep in zip(self.AXIS, res.reports):
            solo = rack_sim(sc)().run(engine="vectorized")
            d1, d2 = rep.to_dict(), solo.to_dict()
            d1["wall_s"] = d2["wall_s"] = 0.0
            assert d1 == d2, f"sweep vs solo diverged on {sc.name}"
            ref = run_engine(rack_sim(sc), "async")
            assert rep.vtime_ns == ref.vtime_ns
            assert rep.tasks == ref.tasks

    def test_sweep_degrade_axis(self):
        """Sweeping a DegradeLink knob: every variant carries the hook
        (same extras shape), only the added latency differs."""
        axis = [Scenario(f"d{e}", (DegradeLink(hosts=(0, 2),
                                               extra_ns=e),))
                for e in (0, 3_000, 11_000)]
        res = rack_sim()().sweep(axis)
        for sc, rep in zip(axis, res.reports):
            ref = run_engine(rack_sim(sc), "async")
            assert rep.vtime_ns == ref.vtime_ns, sc.name
            assert rep.tasks == ref.tasks, sc.name

    def test_sweep_with_kills(self):
        axis = [Scenario("base"),
                Scenario("f", (FailHost(1, at_vtime=160_000),))]
        res = rack_sim()().sweep(axis)
        assert res.reports[0].status == "ok"
        assert res.reports[1].status == "deadlock"
        ref = run_engine(rack_sim(axis[1]), "async")
        assert res.reports[1].tasks == ref.tasks

    def test_sweep_needs_shared_structure(self):
        axis = [Scenario("base"),
                Scenario("i", (Interference(host=0, bursts=3,
                                            burst_ns=1_000),))]
        with pytest.raises(UnsupportedByEngine, match="structure"):
            rack_sim()().sweep(axis)

    def test_sweep_empty_axis(self):
        with pytest.raises(ValueError):
            rack_sim()().sweep([])


# --------------------------------------------------------------------------
# a sweep's shared structure: later variants lower only their values
# --------------------------------------------------------------------------

#: per fleet: its factory, a task, another task, a host pair, a fabric,
#: a host and a vtime inside the run
FLEETS = {
    "rack": (rack_sim, ("w1", "w2", (0, 2), "hub", 1, 160_000)),
    "chip": (chip_sim, ("chip5", "chip2", (0, 1), "dcn", 1, 120_000)),
}

#: per injection kind: (the base scenario's injections, the variant's)
SHARED_CASES = {
    "none": lambda t, u, hosts, fab, host, vt: ((), ()),
    "straggler": lambda t, u, hosts, fab, host, vt: (
        (), (Straggler(t, 2.5),)),
    "two_stragglers": lambda t, u, hosts, fab, host, vt: (
        (), (Straggler(t, 2.5), Straggler(t, 1.5))),
    "fail_at_compute": lambda t, u, hosts, fab, host, vt: (
        (), (FailTask(t, at_compute=2),)),
    "fail_at_vtime": lambda t, u, hosts, fab, host, vt: (
        (), (FailTask(t, at_vtime=vt),)),
    "fail_host": lambda t, u, hosts, fab, host, vt: (
        (), (FailHost(host, at_vtime=vt),)),
    "degrade_fabric": lambda t, u, hosts, fab, host, vt: (
        (), (DegradeLink(fabric=fab, latency_factor=3.0),)),
    "degrade_hosts": lambda t, u, hosts, fab, host, vt: (
        (), (DegradeLink(hosts=hosts, extra_ns=7_000, from_vtime=50_000),)),
    # the base's own straggler must not leak into the shared compute ns
    "base_straggler": lambda t, u, hosts, fab, host, vt: (
        (Straggler(t, 3.0),), (Straggler(u, 1.25),)),
}


@pytest.mark.parametrize("case", sorted(SHARED_CASES))
@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_shared_structure_equals_full_lowering(fleet, case):
    """A variant lowered on the first variant's structure compiles to
    exactly what its own full lowering compiles to: every tape array,
    the tick (shared and solo), tier, bound, round cap and quantities."""
    make, args = FLEETS[fleet]
    base, injections = SHARED_CASES[case](*args)
    sim0 = make(Scenario("base", base))()
    low0 = vectorized._lower(sim0)
    sc = Scenario(case, injections)
    shared_sim = vectorized._variant(sim0, sc)
    full_sim = vectorized._variant(sim0, sc)
    low = vectorized._lower(shared_sim, shared=low0)
    full = vectorized._lower(full_sim)
    assert low["structure"] is low0["structure"]
    assert full["structure"] is not low0["structure"]
    assert shared_sim.placement == full_sim.placement
    tick = math.gcd(*(vectorized._gcd(vectorized._quantities(x))
                      for x in (low0, low)))
    comp0 = vectorized._quantize(low0, tick)
    for got, want in (
            (vectorized._quantize(low, tick, shared=comp0),
             vectorized._quantize(full, tick)),
            (vectorized._quantize(low, None),
             vectorized._quantize(full, None))):
        for f in dataclasses.fields(want.tape):
            a = np.asarray(getattr(got.tape, f.name))
            b = np.asarray(getattr(want.tape, f.name))
            assert a.dtype == b.dtype and np.array_equal(a, b), f.name
        for attr in ("tick_ns", "tier", "tol_ns", "max_rounds",
                     "n_channels", "n_tasks", "task_names", "task_hosts",
                     "markers", "scenario_name"):
            assert getattr(got, attr) == getattr(want, attr), attr
        assert np.array_equal(got.quantities, want.quantities)
    # at the shared tick the structural arrays are the first variant's
    got = vectorized._quantize(low, tick, shared=comp0)
    assert got.tape.op_kind is comp0.tape.op_kind


def lowered_paths(monkeypatch):
    """Record, per ``_lower`` call, whether it was handed a structure."""
    seen = []
    lower = vectorized._lower

    def spy(sim, shared=None):
        seen.append(shared is not None)
        return lower(sim, shared=shared)
    monkeypatch.setattr(vectorized, "_lower", spy)
    return seen


@pytest.mark.parametrize("injections, error, match, shared", [
    ((BitFlip("w1", at_step=0),), UnsupportedByEngine, "BitFlip", True),
    ((ClockSkew(host=1, offset_ns=100),), UnsupportedByEngine,
     "ClockSkew", True),
    ((JoinHost(3, at_vtime=1_000),), UnsupportedByEngine, "joins", True),
    ((Straggler("nope", 2.0),), ValueError, "unknown", True),
    ((FailTask("w0", at_compute=1), FailTask("w0", at_compute=2)),
     ValueError, "two failures", True),
    ((FailHost(99, at_vtime=1_000),), ValueError, "FailHost", True),
    ((DegradeLink(),), ValueError, "exactly one", True),
    ((DegradeLink(hosts=(0, 1), latency_factor=0.1),), ValueError,
     "only add", True),
    ((Interference(host=0, bursts=3, burst_ns=1_000),),
     UnsupportedByEngine, "structure", False),
], ids=["bitflip", "clockskew", "joinhost", "unknown_straggler",
        "two_failtasks", "failhost_range", "degrade_neither",
        "degrade_negative", "interference"])
def test_sweep_variant_checks_on_its_path(monkeypatch, injections, error,
                                          match, shared):
    """A later variant is checked on the shared path as a full lowering
    checks it; one with another Interference list takes the full path
    and is refused by the structure check."""
    seen = lowered_paths(monkeypatch)
    axis = [Scenario("base"), Scenario("bad", injections)]
    with pytest.raises(error, match=match):
        rack_sim()().sweep(axis)
    assert seen == [False, shared]


def test_sweep_refuses_straggled_zero_progress(monkeypatch):
    """A straggler that leaves a tape with PREEMPT_AFTER zero-progress
    computes is refused on the shared path too."""
    seen = lowered_paths(monkeypatch)
    axis = [Scenario("base"), Scenario("z", (Straggler("w2", 0.0),))]
    with pytest.raises(UnsupportedByEngine, match="zero-progress"):
        rack_sim(n_iters=vectorized.PREEMPT_AFTER)().sweep(axis)
    assert seen == [False, True]


# --------------------------------------------------------------------------
# compile-time validation parity with build()
# --------------------------------------------------------------------------

class TestValidationParity:
    def test_unknown_straggler_target(self):
        sc = Scenario("s", (Straggler("nope", 2.0),))
        with pytest.raises(ValueError, match="unknown"):
            rack_sim(sc)().run(engine="vectorized")

    def test_two_explicit_fails(self):
        sc = Scenario("f", (FailTask("w0", at_compute=1),
                            FailTask("w0", at_compute=2)))
        with pytest.raises(ValueError, match="two failures"):
            rack_sim(sc)().run(engine="vectorized")

    def test_degrade_needs_one_of(self):
        sc = Scenario("d", (DegradeLink(),))
        with pytest.raises(ValueError, match="exactly one"):
            rack_sim(sc)().run(engine="vectorized")

    def test_degrade_negative_extra(self):
        sc = Scenario("d", (DegradeLink(hosts=(0, 1),
                                        latency_factor=0.1),))
        with pytest.raises(ValueError, match="only add"):
            rack_sim(sc)().run(engine="vectorized")

    def test_failhost_out_of_range(self):
        sc = Scenario("f", (FailHost(99, at_vtime=1_000),))
        with pytest.raises(ValueError, match="FailHost"):
            rack_sim(sc)().run(engine="vectorized")

    def test_report_metadata(self):
        rep = rack_sim()().run(engine="vectorized")
        assert rep.mode == "vectorized"
        assert rep.tier == "exact"
        assert rep.tick_ns >= 1
        assert rep.sync_rounds > 0          # rounds of the jitted loop
        d = rep.to_dict()                   # JSON round-trip intact
        assert d["tier"] == "exact"
