"""Driver for simulated fleets on the vectorized engine.

The configuration file gives the deployment (pods, chips per pod,
steps, step cost, link speeds); the traffic file gives the call
(``"sweep"``: ``Simulation.sweep`` over ``variants`` straggler
scenarios; ``"run"``: ``Simulation.run(engine="vectorized")`` on one
straggler scenario) and the straggler slowdowns.  Every seed draws the
same multiset of slowdowns, in its own order, on its own chips, so the
work per call does not depend on the seed.

Each call is timed whole, from the call to the returned reports.  The
simulated events a call counts are the reference's count
(``chip_ring.events``: messages and task operations) for each report it
returned, never a count the program reports.  After the window every
report is compared field by field with the plain reference
(``bench/reference/chip_ring.py``) run on the same scenario.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import numpy as np

from reference import chip_ring

#: the report fields an exact-tier run must reproduce
FIELDS = ("status", "n_hosts", "vtime_ns", "messages", "bytes", "tasks",
          "progress", "cells", "live", "links")


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, *,
                 out_dir=None, control: bool = False):
        self.cfg = config
        self.traffic = traffic
        self.rng = np.random.default_rng(seed)
        self.n_chips = config["n_pods"] * config["chips_per_pod"]
        self.events_per_config = chip_ring.events(config)
        # the control: the program's own quantized tier at a tick
        # coarser than the exact one
        self.tick_ns = config["control"]["tick_ns"] if control else None
        self._factors: List[float] = []
        self.calls: List[Dict] = []      # per call: seconds, configs, events
        self.answers: List[tuple] = []   # (stragglers, report)
        self.failures: List[tuple] = []  # (configs, error) per failed call

    # -- inputs ----------------------------------------------------------------
    def _next_factor(self) -> float:
        if not self._factors:
            self._factors = list(self.rng.permutation(
                self.traffic["factors"]))
        return float(self._factors.pop())

    def _stragglers(self) -> List[Dict[str, float]]:
        n = self.traffic.get("variants", 1)
        targets = self.rng.integers(0, self.n_chips, n)
        return [{f"chip{int(t)}": self._next_factor()} for t in targets]

    def _sim(self, stragglers: Dict[str, float]):
        from repro.core.cluster import ClusterSpec, StepCost
        from repro.sim import (ChipRingTraining, Scenario, Simulation,
                               Straggler, Topology)
        c = self.cfg
        spec = ClusterSpec(n_pods=c["n_pods"],
                           chips_per_pod=c["chips_per_pod"],
                           ici_bw_Bps=c["ici_bw_Bps"],
                           ici_lat_ns=c["ici_lat_ns"],
                           dcn_bw_Bps=c["dcn_bw_Bps"],
                           dcn_lat_ns=c["dcn_lat_ns"])
        wl = ChipRingTraining(spec, StepCost(**c["step_cost"]),
                              n_steps=c["n_steps"],
                              skew_bound_ns=c["skew_bound_ns"])
        scenario = Scenario("bench", tuple(
            Straggler(t, f) for t, f in stragglers.items()))
        return Simulation(Topology.single_host(n_cpus=c["host_cpus"]), wl,
                          scenario)

    # -- the timed call --------------------------------------------------------
    def _call(self, record: bool) -> None:
        from repro.sim import Scenario, Straggler
        axis = self._stragglers()
        if self.traffic["call"] == "sweep":
            sim = self._sim({})
            scenarios = [Scenario(f"v{i}", tuple(
                Straggler(t, f) for t, f in s.items()))
                for i, s in enumerate(axis)]
            t0 = time.perf_counter()
            try:
                reports = sim.sweep(scenarios, tick_ns=self.tick_ns).reports
            except Exception as e:    # an answer that never comes
                self.failures.append((len(axis), repr(e)))
                return
            t1 = time.perf_counter()
        else:
            sim = self._sim(axis[0])
            t0 = time.perf_counter()
            try:
                reports = [sim.run(engine="vectorized",
                                   tick_ns=self.tick_ns)]
            except Exception as e:    # an answer that never comes
                self.failures.append((1, repr(e)))
                return
            t1 = time.perf_counter()
        if record:
            self.calls.append({
                "s": t1 - t0, "configs": len(reports),
                "events": len(reports) * self.events_per_config,
                "rounds": sum(r.sync_rounds for r in reports)})
            self.answers.extend(zip(axis, reports))

    def setup(self) -> None:
        """Compile and warm every program the call uses."""
        self._call(record=False)

    def call(self) -> None:
        self._call(record=True)

    # -- numbers ---------------------------------------------------------------
    def end_to_end(self, window_s: float) -> Dict[str, float]:
        return {
            "sweep_configs_per_s":
                sum(c["configs"] for c in self.calls) / window_s,
            "sim_events_per_s":
                sum(c["events"] for c in self.calls) / window_s}

    def counts(self, traced: int) -> Dict:
        calls = self.calls[:traced]
        return {"calls": len(calls),
                "rounds": sum(c["rounds"] for c in calls),
                "n_tasks": self.n_chips, "n_scopes": 1,
                "n_msgs": chip_ring.messages(self.cfg)}

    @contextlib.contextmanager
    def spans(self):
        """Host spans around the calls into each layer of the engine:
        lowering and quantizing (``bench.lower``/``bench.quantize``),
        the round loop (``bench.loop``), decompiling
        (``bench.decompile``) and, inside it, the hub fan-out kernel
        (``bench.hub_route``, held until its result is ready, so that
        the kernel's device time lies inside it)."""
        import jax

        from repro.core import engine_jax
        from repro.kernels import hub_route
        from repro.sim import vectorized
        patched = [(vectorized, "_lower", "bench.lower"),
                   (vectorized, "_quantize", "bench.quantize"),
                   (vectorized, "_decompile", "bench.decompile"),
                   (engine_jax, "run_vec_tape_batch", "bench.loop"),
                   (hub_route, "hub_route", "bench.hub_route")]
        saved = [(m, a, getattr(m, a)) for m, a, _ in patched]

        def wrap(fn, label):
            def inner(*args, **kw):
                with jax.profiler.TraceAnnotation(label):
                    out = fn(*args, **kw)
                    if label == "bench.hub_route":
                        jax.block_until_ready(out)
                    return out
            return inner
        for (m, a, label), (_, _, fn) in zip(patched, saved):
            setattr(m, a, wrap(fn, label))
        try:
            yield
        finally:
            for m, a, fn in saved:
                setattr(m, a, fn)

    def release(self) -> None:
        """Reports are plain host data: nothing on the device to free."""

    # -- correctness -----------------------------------------------------------
    def check(self):
        """Every report of the window against the reference: the number
        of reports that differ in any field, and of configurations whose
        call failed (limit 0 each)."""
        bad = failed = 0
        for stragglers, report in self.answers:
            ref = chip_ring.simulate(self.cfg, stragglers)
            if any(getattr(report, f) != ref[f] for f in FIELDS):
                bad += 1
            if report.status != "ok":
                failed += 1
        lost = sum(n for n, _ in self.failures)
        if not self.answers and not lost:
            bad = 1                     # nothing compared is no pass
        checks = {"reports_differing": {"value": bad, "limit": 0,
                                        "of": len(self.answers)},
                  "configs_failed": {"value": lost, "limit": 0}}
        if self.failures:
            checks["configs_failed"]["error"] = self.failures[0][1]
        return checks, len(self.answers) + lost, failed + lost
