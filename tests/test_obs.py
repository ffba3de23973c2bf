"""The program's own spans and compile counter (``repro.obs``): span
nesting per thread, builds credited to the innermost span and counted
as the benchmark's ``CompileCounter`` counts them, charged live spans
that enclose exactly what the ledger charges, the vectorized engine's
reports unchanged with spans recorded, and its timers covering whole
calls."""
import collections
import contextlib
import glob
import importlib.util
import pathlib
import statistics
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import engine_jax
from repro.sim import RackRing, Scenario, Simulation, Straggler, Topology
from repro.sim import vectorized

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"


def bench_module(name):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name, BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rack_sim(sc=None):
    wl = RackRing(n_racks=2, hosts_per_rack=2, n_iters=6, compute_ns=5_000,
                  msg_bytes=4096, cross_every=2, skew_bound_ns=100_000)
    return Simulation(Topology.racks(2, 2), wl, sc)


@contextlib.contextmanager
def profiled(tmp_path, out):
    """Record a profiler trace of the block, with the benchmark's window
    span around it; ``out`` gets the trace's ProfileData."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    out.append(ProfileData.from_file(path))


def host_events(pdata, name):
    return sorted((e for p in pdata.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name == name), key=lambda e: e.start_ns)


def test_spans_nest_on_their_own_thread():
    inner = obs.span("b", k="v")
    assert obs.stack() == ()
    with obs.span("a"):
        with inner:
            with inner:     # one span object entered again, nested
                assert obs.stack() == ("a", "b", "b")
            seen = []
            t = threading.Thread(target=lambda: seen.append(obs.stack()))
            t.start()
            t.join(timeout=30)
            assert not t.is_alive() and seen == [()]
        assert obs.stack() == ("a",)
    with pytest.raises(KeyError):
        with obs.span("c"):
            raise KeyError("x")
    assert obs.stack() == ()


def test_build_outside_any_span_is_credited_to_none():
    x = jnp.arange(5)
    before = obs.counters()["compile"].get(None, 0)
    jax.jit(lambda x: x * 3 + 1)(x).block_until_ready()
    assert obs.counters()["compile"].get(None, 0) == before + 1


def test_build_in_the_loop_credited_to_it_and_counted_as_the_harness_does(
        tmp_path, monkeypatch):
    """A build planted inside the round loop's call: each run call
    builds once more, credited to ``sim.loop``; the compile counter, the
    benchmark's ``CompileCounter`` and the ``compiles_per_call`` reader
    of a trace of the same calls all count one build per call."""
    loop = engine_jax.run_vec_tape

    def planted(*args, **kw):
        jax.jit(lambda x: x + 1)(jnp.int32(0)).block_until_ready()
        return loop(*args, **kw)
    monkeypatch.setattr(engine_jax, "run_vec_tape", planted)
    sc = Scenario("s", (Straggler("w1", 2.0),))
    rack_sim(sc).run(engine="vectorized")           # warm every shape
    harness = bench_module("harness")
    counter = harness.CompileCounter()
    before = obs.counters()["compile"]
    calls, pdata = 3, []
    counter.on = True
    with profiled(tmp_path, pdata):
        for _ in range(calls):
            rack_sim(sc).run(engine="vectorized")
    counter.on = False
    after = obs.counters()["compile"]
    grown = {k: n - before.get(k, 0) for k, n in after.items()
             if n != before.get(k, 0)}
    assert grown == {"sim.loop": calls}
    assert counter.n == calls
    trace = bench_module("tracereduce").Trace.from_profile(pdata[0])
    builds = host_events(pdata[0], "livestack.compile")
    assert [dict(e.stats)["span"] for e in builds] == ["sim.loop"] * calls
    monkeypatch.syspath_prepend(str(BENCH))     # as the readers import
    import progtrace
    ctx = type("Ctx", (), {"trace": trace,
                           "traffic": {"trace_calls": calls},
                           "program": progtrace.ProgramSpans(
                               trace, progtrace.events(pdata[0]))})
    read = harness.load_reader("compiles_per_call.run")
    assert read(ctx) == counter.n / calls == 1.0


def test_charged_decode_spans_enclose_the_charged_cost(tmp_path):
    from repro.sim import record_live_serve
    pdata = []
    with profiled(tmp_path / "trace", pdata):
        _report, ledger = record_live_serve(
            tmp_path / "serve.json", n_requests=2, max_batch=2,
            decode_steps=4, arrivals=[1, 2])
    charged = [e for es in ledger.tasks.values() for e in es
               if e["label"].startswith("decode:")]
    spans = host_events(pdata[0], "livestack.live.decode")
    assert [dict(e.stats)["label"] for e in spans] \
        == [e["label"] for e in charged]
    over = [s.duration_ns - e["cost_ns"] for s, e in zip(spans, charged)]
    # each span encloses its charged interval (two clocks: 1 us slack),
    # and what it adds is its own entry and exit, microseconds
    assert min(over) > -1_000
    assert statistics.median(over) < 200_000
    for child in ("serve.step", "serve.sample", "serve.sync"):
        assert len(host_events(pdata[0], "livestack." + child)) \
            >= len(charged)


def test_sweep_reports_unchanged_with_spans_recorded(tmp_path):
    axis = [Scenario("base"), Scenario("s1", (Straggler("w1", 2.0),)),
            Scenario("s2", (Straggler("w3", 3.0),))]
    plain = rack_sim().sweep(axis)
    pdata = []
    with profiled(tmp_path, pdata):
        traced = rack_sim().sweep(axis)
    assert len(host_events(pdata[0], "livestack.sim.sweep")) == 1
    for name in ("sim.lower", "sim.unstack", "sim.decompile"):
        assert len(host_events(pdata[0], "livestack." + name)) == len(axis)
    for a, b in zip(plain.reports, traced.reports):
        da, db = a.to_dict(), b.to_dict()
        da["wall_s"] = db["wall_s"] = 0.0
        assert da == db


def test_sweep_lowers_each_variant_once_and_marks_its_path(tmp_path,
                                                          monkeypatch):
    """Wrapped by name as the benchmark wraps them, ``_lower`` and
    ``_quantize`` run once per variant, and every variant after the
    first lowers on the first one's structure: its ``sim.lower`` span
    says ``path="shared"``."""
    calls = collections.Counter()

    def counting(name):
        fn = getattr(vectorized, name)

        def inner(*args, **kw):
            calls[name] += 1
            return fn(*args, **kw)
        return inner
    for name in ("_lower", "_quantize"):
        monkeypatch.setattr(vectorized, name, counting(name))
    axis = [Scenario(f"s{i}", (Straggler(f"w{i % 4}", 1.0 + i / 4),))
            for i in range(5)]
    pdata = []
    with profiled(tmp_path, pdata):
        rack_sim().sweep(axis)
    assert calls == {"_lower": len(axis), "_quantize": len(axis)}
    spans = host_events(pdata[0], "livestack.sim.lower")
    assert [dict(e.stats)["path"] for e in spans] \
        == ["full"] + ["shared"] * (len(axis) - 1)


def slowed(fn, s):
    def inner(*args, **kw):
        time.sleep(s)
        return fn(*args, **kw)
    return inner


def test_run_wall_covers_decompile(monkeypatch):
    monkeypatch.setattr(vectorized, "_decompile",
                        slowed(vectorized._decompile, 0.2))
    t0 = time.perf_counter()
    report = rack_sim().run(engine="vectorized")
    outer = time.perf_counter() - t0
    assert 0.2 <= report.wall_s <= outer


def test_sweep_wall_covers_lowering_and_decompile(monkeypatch):
    axis = [Scenario("base"), Scenario("s1", (Straggler("w1", 2.0),))]
    monkeypatch.setattr(vectorized, "_lower", slowed(vectorized._lower, 0.1))
    monkeypatch.setattr(vectorized, "_decompile",
                        slowed(vectorized._decompile, 0.1))
    t0 = time.perf_counter()
    res = rack_sim().sweep(axis)
    outer = time.perf_counter() - t0
    assert 0.4 <= res.wall_s <= outer
    assert res.configs_per_s == pytest.approx(len(axis) / res.wall_s)
    assert all(r.wall_s == pytest.approx(res.wall_s / len(axis))
               for r in res.reports)
