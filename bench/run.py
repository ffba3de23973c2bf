#!/usr/bin/env python3
"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are listed in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown`` of device time and idle gaps, and last
``checks``, each compared number with its limit (also the last lines of
standard error).  Without a TPU, or with fewer chips than the cell
needs, it exits with code 1 and prints no result.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root
of the checkout; run outputs (the profiler trace, the live ledger, the
TPU runtime's logs) go to ``.bench_out/``.
"""
import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# JAX and the TPU runtime write into these only if they exist
for _d in (ROOT / ".jax_cache", ROOT / ".bench_out" / "tpu_logs"):
    _d.mkdir(parents=True, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        spec = harness.load_spec(ROOT)
        cell, *_ = harness.cell_parts(spec, args.workload)
        devices = harness.require_devices(cell["chips"])
    except harness.CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    result = harness.measure(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, devices=devices, spec=spec,
        log=lambda s: print(s, file=sys.stderr))
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
