"""The benchmark's generic part: one run of one cell.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
configuration file names its driver (``bench/drivers/<driver>.py``),
which builds the system under test, makes its inputs from the seed,
makes one whole user-facing call at a time, and judges what the calls
produced against the configuration's plain reference
(``bench/reference/``).  The traffic mix is a data file
(``bench/traffic/<traffic>.json``) that the driver's generator reads.
Each per-layer metric is a reader of its own
(``bench/metrics/<metric>.py``) that takes its number from the trace,
the program's spans or the driver's counts, and returns None when it
finds nothing to read.  Adding a cell or a metric adds files; it edits
none of these.

A driver module defines ``Driver(config, traffic, seed, out_dir=...)``
with ``setup()``, ``call()`` (one whole timed call), the lists ``calls``
and ``failures``, ``end_to_end(window_s)`` (metric name -> value),
``counts(n_traced_calls)`` and ``spans()`` (a context that adds host
spans while tracing) for the readers, ``release()`` (free the program's
state) and ``check()`` (``checks, attempted, failed``).

A run: set-up (weights, data, warm-up of every shape the cell uses);
then the window, whole calls back to back until ``seconds`` have passed
(the last call ends the window); with ``trace`` the profiler records
the first ``trace_calls`` calls of the window; then the device's peak
memory is read, the program's state is freed and the reference check
runs.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import sys
import time
import types
from typing import Any, Dict, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class CellError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


def load_spec(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(spec: Dict[str, Any], name: str):
    """(cell, configuration entry, configuration file, traffic file)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, entry, config, traffic


def metrics_of(spec: Dict[str, Any], cell: Dict[str, Any]):
    """(end-to-end metric entries, per-layer metric entries) the cell
    reports: an end-to-end metric without a ``workloads`` list (set-up
    time) belongs to every cell, any other metric to the cells its
    ``workloads`` list names."""
    name = cell["name"]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in spec["per_layer"] if name in m["workloads"]]
    return e2e, layer


def require_devices(chips: int):
    """The devices, or CellError when JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX's first device is "
                        f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise CellError(f"the cell needs {chips} chips, "
                        f"{len(devs)} present")
    return devs


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise CellError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json")
    return table[device_kind]


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads while on."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, **_kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event, **_kw):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.n += 1


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def measure(cell_name: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, devices=None, spec=None, config=None,
            traffic=None, out_dir: Optional[pathlib.Path] = None,
            peaks=None, log=print) -> Dict[str, Any]:
    """One run of ``cell_name``; returns the result object.  ``devices``
    None skips the look for a chip (tests).  ``config``/``traffic``
    replace the cell's files and ``peaks`` the device's row of
    ``peaks.json`` (tests run small sizes on the CPU)."""
    import jax
    spec = spec or load_spec()
    cell, _entry, cfg_file, traffic_file = cell_parts(spec, cell_name)
    config = config or cfg_file
    traffic = traffic or traffic_file
    e2e_entries, layer_entries = metrics_of(spec, cell)
    out_dir = out_dir or ROOT / ".bench_out"
    out_dir.mkdir(parents=True, exist_ok=True)
    used = (devices or jax.devices())[:cell["chips"]]

    driver_mod = importlib.import_module(f"drivers.{config['driver']}")
    driver = driver_mod.Driver(config, traffic, seed, out_dir=out_dir)
    counter = CompileCounter()
    driver.setup()
    setup_s = time.perf_counter() - t_start

    trace_calls = int(traffic.get("trace_calls", 1)) if trace else 0
    trace_dir = out_dir / "trace"
    counter.on = True
    t0 = time.perf_counter()
    traced = None
    if trace_calls:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with driver.spans():
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    for _ in range(trace_calls):
                        driver.call()
            finally:
                jax.profiler.stop_trace()
        traced = len(driver.calls)
    while time.perf_counter() - t0 < seconds:
        driver.call()
    window_s = time.perf_counter() - t0
    counter.on = False
    log(f"window: {window_s:.3f} s, {len(driver.calls)} calls, "
        f"{len(driver.failures)} failed, "
        f"{counter.n} compilations or cache loads inside it")

    values = driver.end_to_end(window_s)
    values["setup_s"] = setup_s
    mem = memory_peak_bytes(used)
    counts = driver.counts(traced) if trace_calls else {}
    driver.release()
    t_check = time.perf_counter()
    checks, attempted, failed = driver.check()
    log(f"reference check: {time.perf_counter() - t_check:.3f} s")

    metrics: Dict[str, Dict[str, Any]] = {}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices or jax.devices()),
              "memory_peak_bytes": mem}
    breakdown = None
    if trace_calls:
        import tracereduce
        tr = tracereduce.Trace.load(trace_dir, n_devices=len(used))
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
        ctx = types.SimpleNamespace(
            trace=tr, counts=counts, config=config, traffic=traffic,
            peaks=peaks or peaks_for(used[0].device_kind))
        for m in layer_entries:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in e2e_entries:
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def print_result(result: Dict[str, Any]) -> None:
    for name, c in result["checks"].items():
        of = f" of {c['of']}" if "of" in c else ""
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}){of}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
