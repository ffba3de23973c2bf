#!/usr/bin/env python3
"""Readings from which each cell's correctness limit is set.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... [--control-seeds 3]

For each seed, in one process: set the cell up as a run does, make
``calls`` whole calls of the window's kind (one wave for serving), and
read the cell's compared number for the program and for its control
(the first ``--control-seeds`` seeds): for a simulated fleet the
program's coarser-tick tier, for a served model the reference computed
in float8.  One JSON line per seed, then a summary: the lower reading
(the largest the program gives), the upper reading (the smallest the
control gives) and their ratio.  The benchmark's own runs never run
this; it needs a TPU like they do.
"""
import argparse
import gc
import importlib
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# JAX and the TPU runtime write into these only if they exist
for _d in (ROOT / ".jax_cache", ROOT / ".bench_out" / "tpu_logs"):
    _d.mkdir(parents=True, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))


def readings(cell: str, seed: int, control: bool, *, spec, calls=1,
             config=None, traffic=None, out_dir=None):
    """(program's compared number, control's or None, its name)."""
    import harness
    c, _entry, cfg_file, traffic_file = harness.cell_parts(spec, cell)
    config = config or cfg_file
    traffic = traffic or traffic_file
    mod = importlib.import_module(f"drivers.{config['driver']}")
    out_dir = out_dir or ROOT / ".bench_out"
    out_dir.mkdir(parents=True, exist_ok=True)
    low = ctl = None
    if config["driver"] == "live_serve":
        drv = mod.Driver(config, traffic, seed, out_dir=out_dir)
        drv.setup()
        for _ in range(calls):
            drv.call()
        drv.release()
        low = float(drv.gaps().max())
        if control:
            ctl = float(drv.gaps("fp8").max())
        name = "widest_logit_gap"
        del drv
    else:
        for is_control in (False, True) if control else (False,):
            drv = mod.Driver(config, traffic, seed, out_dir=out_dir,
                             control=is_control)
            drv.setup()
            for _ in range(calls):
                drv.call()
            checks, _, _ = drv.check()
            name, value = next(iter(checks.items()))
            if is_control:
                ctl = value["value"]
            else:
                low = value["value"]
            del drv
    gc.collect()
    return low, ctl, name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--calls", type=int, default=1)
    args = ap.parse_args(argv)
    import harness
    spec = harness.load_spec(ROOT)
    cell, *_ = harness.cell_parts(spec, args.workload)
    try:
        harness.require_devices(cell["chips"])
    except harness.CellError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    lows, ctls = [], []
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        low, ctl, name = readings(args.workload, seed,
                                  i < args.control_seeds, spec=spec,
                                  calls=args.calls)
        lows.append(low)
        if ctl is not None:
            ctls.append(ctl)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "number": name, "program": low, "control": ctl,
                          "s": time.perf_counter() - t0}), flush=True)
    lower, upper = max(lows), (min(ctls) if ctls else None)
    print(json.dumps({"workload": args.workload, "number": name,
                      "seeds": len(lows), "control_seeds": len(ctls),
                      "lower": lower, "upper": upper,
                      "ratio": (upper / lower if upper is not None and lower
                                else None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
