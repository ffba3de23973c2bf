"""The trace reduction and the kernel readers, on a hand-made trace and
on one steady ``ring2048.run`` call recorded on a TPU v5e (the device
plane's ops and programs, cut to that call's window)."""
import types

import pytest
from conftest import BENCH, load

import harness
import tracereduce
from jax.profiler import ProfileData


def plane(pid, name, lines):
    meta, out = {}, [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, events) in enumerate(lines, 1):
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for start, dur, ev in events:
            mid = meta.setdefault(ev, len(meta) + 1)
            out.append(f"events {{ metadata_id: {mid} "
                       f"offset_ps: {start * 1000} "
                       f"duration_ps: {dur * 1000} }}")
        out.append("}")
    out += [f'event_metadata {{ key: {m} value {{ id: {m} name: "{n}" }} }}'
            for n, m in meta.items()]
    return " ".join(out) + " }"


def small_trace():
    """Window 100..200 ns; a loop 110..150 holding ops 110..120 and
    130..150; an op 160..170 and one that starts before the window."""
    dev = plane(1, "/device:TPU:0", [
        ("XLA Ops", [(90, 15, "%copy.1 = copy()"),
                     (110, 40, "%while.2 = while()"),
                     (110, 10, "%fusion.3 = fusion()"),
                     (130, 20, "%fusion.4 = fusion()"),
                     (160, 10, "%fusion.3 = fusion()")]),
        ("XLA Modules", [(90, 15, "jit_copy(1)"),
                         (110, 60, "jit_step(2)")])])
    host = plane(2, "/host:CPU", [
        ("python3", [(100, 100, "bench.window"),
                     (100, 55, "bench.lower"),
                     (150, 50, "bench.decompile"),
                     (90, 5, "other")])])
    return tracereduce.Trace.from_profile(
        ProfileData.from_text_proto(dev + "\n" + host))


def test_busy_idle_and_window():
    tr = small_trace()
    assert tr.window_s() == pytest.approx(100e-9)
    # 100..105 (clipped copy) + 110..150 + 160..170
    assert tr.busy_s() == pytest.approx(55e-9)
    assert tr.idle_pct() == pytest.approx(45.0)


def test_top_ops_count_leaves_only():
    ops = dict(tracereduce.Trace.top_ops(small_trace(), 10))
    assert "while.2" not in ops
    assert ops["fusion.3"] == pytest.approx(20e-9)
    assert ops["fusion.4"] == pytest.approx(20e-9)
    assert ops["copy.1"] == pytest.approx(5e-9)


def test_idle_gaps_named_by_the_innermost_host_span():
    gaps = dict(small_trace().idle_gaps(10))
    # idle 105..110 under bench.lower; 150..160 and 170..200 under
    # bench.decompile
    assert gaps == pytest.approx({"bench.lower": 5e-9,
                                  "bench.decompile": 40e-9})


def test_spans_and_modules():
    tr = small_trace()
    assert tr.span_ns("bench.lower") == 55
    assert tr.span_ns("bench.decompile") == 50
    assert tr.module_ns("jit_step") == 60
    assert tr.module_ns("jit_copy") == 5       # clipped to the window


def test_op_events_inside_a_program_or_a_host_span():
    tr = small_trace()

    def fusion(n):
        return "fusion" in n
    assert len(tr.op_events(fusion)) == 3
    assert len(tr.op_events(fusion, module="jit_step")) == 3
    assert tr.op_events(fusion, module="jit_copy") == []
    assert [e[0] for e in tr.op_events(fusion, span="bench.lower")] \
        == [110, 130]
    assert [e[0] for e in tr.op_events(fusion, span="bench.decompile")] \
        == [160]


def test_trace_without_window_is_refused():
    host = plane(2, "/host:CPU", [("python3", [(0, 10, "bench.lower")])])
    with pytest.raises(ValueError):
        tracereduce.Trace.from_profile(ProfileData.from_text_proto(host))


@pytest.fixture(scope="module")
def chip_call():
    text = (BENCH / "tests" / "data" / "ring2048_run_call.pbtxt").read_text()
    return tracereduce.Trace.from_profile(ProfileData.from_text_proto(text))


def ctx(trace, **counts):
    return types.SimpleNamespace(
        trace=trace, counts=counts, config=load("configs/ring2048.json"),
        traffic=load("traffic/run.json"),
        peaks=load("peaks.json")["devices"]["TPU v5 lite"])


def test_recorded_call(chip_call):
    assert 0 < chip_call.busy_s() < chip_call.window_s()
    assert chip_call.module_ns("jit_run_vec_tape") > 0
    # the round loop's enclosing while op is never a top op
    assert not any(n.startswith("while")
                   for n, _ in chip_call.top_ops(10))


def test_kernel_readers_on_the_recorded_call(chip_call):
    # the recorded call ran 26 rounds: two minskew Pallas calls per
    # round inside the round loop's program, one hub_route pass over
    # 8224 messages, run eagerly (program ``jit_wrapped``) while
    # decompiling
    c = ctx(chip_call, calls=1, rounds=26, n_tasks=2048, n_scopes=1,
            n_msgs=8224)
    assert len(chip_call.op_events(
        lambda n: "tpu_custom_call" in n and " s8[" in n,
        module="jit_run_vec_tape")) == 52
    minskew = harness.load_reader("minskew_roofline")(c)
    assert 0 < minskew < 100
    assert harness.load_reader("loop_ms.sweep")(c) > 0
    # the call was recorded without the benchmark's span around the
    # kernel's entry: a Pallas call outside any such span is not read
    assert harness.load_reader("hub_route_roofline")(c) is None
    # the span as the benchmark's wrapper sets it, around the kernel's
    # entry until its result is ready, that is around its program
    (s, e), = [(s, e) for s, e, n in chip_call.modules[0]
               if n.startswith("jit_wrapped")]
    with_span = tracereduce.Trace(
        chip_call.ops, chip_call.modules,
        chip_call.spans + [(s - 1000, e + 1000, "bench.hub_route")])
    hub = harness.load_reader("hub_route_roofline")(ctx(
        with_span, n_msgs=8224))
    assert 0 < hub < 100


def test_readers_find_nothing_without_events():
    c = ctx(small_trace(), calls=0, rounds=0, n_tasks=2048, n_scopes=1,
            n_msgs=8224)
    for name in ("minskew_roofline", "hub_route_roofline", "lower_ms.run",
                 "decompile_ms.run", "decode_mfu"):
        assert harness.load_reader(name)(c) is None
