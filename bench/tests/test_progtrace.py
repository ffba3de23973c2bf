"""The program's own spans (``livestack.*``) in a trace, and the readers
of them: on a hand-made trace, on one steady ``ring2048.run`` call
recorded on a TPU v5e with the program's spans kept, and in whole traced
runs of each cell at small sizes on the CPU."""
import pathlib
import types

import pytest
from conftest import BENCH, load, small_decode, small_qwen3, small_ring

import harness
import progtrace
import tracereduce
from jax.profiler import ProfileData
from test_tracereduce import plane, small_trace

PEAKS = load("peaks.json")["devices"]["TPU v5 lite"]
NEW_READERS = ("marshal_ms.sweep", "fanout_ms.run", "compiles_per_call.sweep",
               "compiles_per_call.run", "compiles_per_call.decode",
               "charged_idle_ms.decode")


def ctx(trace, program, trace_calls=1, **counts):
    """A reader's context with the program's events already read."""
    return types.SimpleNamespace(
        trace=trace, counts=counts, config=load("configs/ring2048.json"),
        traffic={"trace_calls": trace_calls}, peaks=PEAKS,
        program=progtrace.ProgramSpans(trace, program) if program else None)


RUN_CALL = [
    (102, 198, "livestack.sim.run"), (102, 112, "livestack.sim.lower"),
    (112, 152, "livestack.sim.loop"), (152, 190, "livestack.sim.decompile"),
    (155, 175, "livestack.sim.fanout"), (156, 157, "livestack.compile"),
    (20, 30, "livestack.compile")]          # before the window
"""The program's spans of one run call on ``small_trace``: sim.run
102..198 holding sim.lower 102..112, sim.loop 112..152 and sim.decompile
152..190, which holds sim.fanout 155..175 and a build at 156..157."""


def program_spans():
    return progtrace.ProgramSpans(small_trace(), RUN_CALL)


def test_program_spans_lengths_and_counts():
    p = program_spans()
    assert p.span_ns("livestack.sim.fanout") == 20
    assert p.span_ns("livestack.sim.run") == 96
    assert p.count("livestack.compile") == 1
    assert p.count("livestack.sim.lower") == 1
    assert p.count("livestack.sim.stage") == 0


def test_program_idle_inside_each_span():
    p = program_spans()
    # idle is 105..110, 150..160 and 170..200
    assert p.idle_in("livestack.sim.fanout") == [10]
    assert p.idle_in("livestack.sim.decompile") == [28]
    assert p.idle_in("livestack.sim.run") == [43]
    assert p.idle_in("livestack.sim.stage") == []


def test_program_idle_gaps_cut_at_span_boundaries():
    gaps = dict(program_spans().idle_gaps(10))
    assert gaps == pytest.approx({
        "livestack.sim.lower": 5e-9, "livestack.sim.loop": 2e-9,
        "livestack.sim.decompile": 18e-9, "livestack.sim.fanout": 10e-9,
        "livestack.sim.run": 8e-9, "bench.window": 2e-9})
    assert sum(gaps.values()) == pytest.approx(
        small_trace().window_s() * small_trace().idle_pct() / 100)


def test_events_keep_only_the_programs_host_events():
    host = plane(2, "/host:CPU", [
        ("python3", [(100, 100, "bench.window"), (102, 96, "livestack.sim.run"),
                     (155, 20, "livestack.sim.fanout"), (90, 5, "other")])])
    dev = plane(1, "/device:TPU:0", [("XLA Ops", [(110, 5, "livestack.x")])])
    pdata = ProfileData.from_text_proto(dev + "\n" + host)
    assert sorted(progtrace.events(pdata)) == [
        (102, 198, "livestack.sim.run"), (155, 175, "livestack.sim.fanout")]
    # the benchmark's reduction is blind to them
    assert [n for _, _, n in tracereduce.Trace.from_profile(pdata).spans] \
        == ["bench.window"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_none_without_program_spans(name):
    c = ctx(small_trace(), [], calls=1)
    assert harness.load_reader(name)(c) is None


def test_new_readers_read_program_spans():
    c = ctx(small_trace(), RUN_CALL, trace_calls=2, calls=2)
    read = harness.load_reader
    assert read("fanout_ms.run")(c) == pytest.approx(20 / 2 * 1e-6)
    # no stage or unstack span: a reading of 0, not None
    assert read("marshal_ms.sweep")(c) == 0.0
    for cell in ("run", "sweep", "decode"):
        assert read(f"compiles_per_call.{cell}")(c) == 0.5
    decode = [(100, 150, "livestack.live.decode"),
              (150, 200, "livestack.live.decode")]
    # idle 5 ns in the first step, 10 + 30 ns in the second
    assert read("charged_idle_ms.decode")(ctx(small_trace(), decode)) \
        == pytest.approx(22.5e-6)


def test_of_finds_the_profile_the_harness_loaded(tmp_path):
    """``of`` reads the profile from the ``trace_dir`` of the frame
    that holds the trace, as ``harness.measure`` does, and keeps the
    result on the context."""
    import jax
    from repro import obs
    trace_dir = pathlib.Path(tmp_path) / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with obs.span("sim.fanout"):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    tr = tracereduce.Trace.load(trace_dir)
    c = types.SimpleNamespace(trace=tr)
    p = progtrace.of(c)
    assert p is not None and c.program is p
    assert p.count("livestack.sim.fanout") == 1
    assert progtrace.of(c) is p
    # a trace that no frame holds beside a directory reads nothing
    other = types.SimpleNamespace(trace=small_trace())
    assert progtrace.of(other) is None and other.program is None


@pytest.fixture(scope="module")
def spans_call():
    """One steady ``ring2048.run`` call recorded on a TPU v5e with the
    program's spans (``livestack.*``) and the benchmark's, cut to a
    window of the call's ``sim.run`` span plus half a millisecond on
    each side: (reduced trace, program spans)."""
    text = (BENCH / "tests" / "data" / "ring2048_run_spans.pbtxt").read_text()
    pdata = ProfileData.from_text_proto(text)
    tr = tracereduce.Trace.from_profile(pdata)
    return tr, progtrace.ProgramSpans(tr, progtrace.events(pdata))


def test_program_readers_on_a_recorded_call(spans_call):
    tr, p = spans_call
    c = ctx(tr, p.program, calls=1)
    read = harness.load_reader
    assert read("fanout_ms.run")(c) == pytest.approx(83.850478)
    # the two eager hub_route passes each built a program, inside the
    # fan-out span
    assert read("compiles_per_call.run")(c) == 2.0
    fanout = [(s, e) for s, e, n in p.program if n == "livestack.sim.fanout"]
    builds = [(s, e) for s, e, n in p.program if n == progtrace.COMPILE]
    assert len(builds) == 2 and all(
        any(a <= s and e <= b for a, b in fanout) for s, e in builds)


def test_program_idle_gaps_on_a_recorded_call(spans_call):
    tr, p = spans_call
    gaps = p.idle_gaps(20)
    idle = tr.window_s() * tr.idle_pct() / 100
    assert sum(v for _, v in gaps) == pytest.approx(idle)
    assert [n for n, _ in gaps[:2]] == ["livestack.sim.lower",
                                        "livestack.sim.fanout"]
    # at least 95 % of the idle time inside the call lies in a named
    # child span of sim.run
    inside, = p.idle_in("livestack.sim.run")
    assert dict(gaps)["livestack.sim.run"] < 0.05 * inside * 1e-9


def test_kernel_names_reach_the_recorded_ops(spans_call):
    tr, _ = spans_call
    names = {tracereduce.short_name(n).rsplit(".", 1)[0]
             for _, _, n in tr.ops[0] if "tpu_custom_call" in n}
    assert names == {"minskew_minima", "minskew_eligible", "hub_route"}


@pytest.mark.parametrize("cell", ["ring2048.run", "ring2048.sweep32",
                                  "qwen3_4b.decode"])
def test_traced_run_reports_the_program_metrics(cell, tmp_path):
    """A whole traced run of the cell, through the unchanged harness,
    reports every new metric of the cell."""
    config, traffic = ((small_qwen3(), small_decode())
                       if cell == "qwen3_4b.decode" else (small_ring(), None))
    r = harness.measure(cell, 2**33 + 7, 1.0, True, t_start=0.0,
                        config=config, traffic=traffic,
                        out_dir=pathlib.Path(tmp_path), peaks=PEAKS,
                        log=lambda _s: None)
    assert r["correct"], r["checks"]
    _, layer = harness.metrics_of(harness.load_spec(), {"name": cell})
    new = {m["name"] for m in layer} & set(NEW_READERS)
    assert new and new <= set(r["metrics"])
