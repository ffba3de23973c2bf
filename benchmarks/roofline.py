"""Roofline analysis from the dry-run artifacts (deliverable g).

Per (arch x shape x mesh):
  compute term    = HLO_FLOPs_per_chip / peak_FLOPs      [s]
  memory term     = HLO_bytes_per_chip / HBM_bw          [s]
  collective term = collective_bytes_per_chip / link_bw  [s]
(the dry-run stores per-partition numbers: cost_analysis runs on the
post-SPMD module, and collective bytes are parsed from per-partition HLO
shapes with a ring cost model — all-gather counts result bytes,
all-reduce counts 2x operand bytes.)

Also: MODEL_FLOPS (6*N_active*tokens for train, 2*N_active*tokens for
inference; embedding-table lookups excluded, lm_head included, MoE
experts scaled by top_k/n_experts) and the useful-compute ratio
MODEL_FLOPS / HLO_FLOPs, which surfaces remat recompute, padding waste,
and replicated-attention redundancy.
"""
from __future__ import annotations

import json
import math
import pathlib
import sys
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12          # TPU v5e bf16
HBM_BW = 819e9               # B/s
LINK_BW = 50e9               # B/s per ICI link

ROOT = pathlib.Path(__file__).resolve().parents[1]
DRYRUN = ROOT / "results" / "dryrun"


def n_active_params(arch: str) -> float:
    """Non-embedding active params (MoE experts scaled by top_k/E)."""
    from repro import configs
    from repro.models import registry

    cfg = configs.get(arch)
    specs = registry.param_specs(cfg)
    import jax

    total = 0.0
    for path, leaf in jax.tree.leaves_with_path(specs):
        name = jax.tree_util.keystr(path)
        size = math.prod(leaf.shape)
        if "embed" in name and "lm_head" not in name:
            continue                      # lookup, not matmul
        if cfg.n_experts and any(w in name for w in
                                 ("w_gate", "w_up", "w_down")) \
                and "moe" in name:
            size *= cfg.top_k / cfg.n_experts
        total += size
    return total


def model_flops_per_chip(rec: Dict) -> float:
    from repro.launch import shapes as shp

    arch, shape_name = rec["arch"], rec["shape"]
    shape = shp.SHAPES[shape_name]
    n_act = n_active_params(arch)
    chips = rec["n_chips"]
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * n_act * tokens / chips
    if shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * n_act * tokens / chips
    # decode: one token per sequence
    return 2.0 * n_act * shape.batch / chips


def corrected_for(rec: Dict) -> Optional[Dict]:
    """Trip-count-corrected costs from launch/costcount.py, if present."""
    f = (DRYRUN.parent / "costs"
         / f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
    if f.exists():
        c = json.loads(f.read_text())
        if c.get("status") == "ok":
            return c["corrected"]
    return None


def analyze(rec: Dict) -> Optional[Dict]:
    if rec["status"] != "ok":
        return None
    corr = corrected_for(rec)
    if corr is not None:
        flops = corr["flops"]
        bts = corr["bytes"]
        coll_bytes = corr["coll_bytes"]
        coll = {"count": corr["coll_count"]}
        source = "corrected"
    else:
        flops = rec["flops_per_chip"]
        bts = rec["bytes_per_chip"]
        coll = rec["collectives"]
        coll_bytes = sum(v for k, v in coll.items() if k != "count")
        source = "raw"
    t_c = flops / PEAK_FLOPS
    t_m = bts / HBM_BW
    t_n = coll_bytes / LINK_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_n}
    dom = max(terms, key=terms.get)
    bound = max(t_c, t_m, t_n)
    mf = model_flops_per_chip(rec)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "source": source,
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "dominant": dom,
        "step_s_lower_bound": bound,
        "roofline_frac": t_c / bound if bound > 0 else 0.0,
        "model_flops_per_chip": mf,
        "useful_ratio": mf / flops if flops > 0 else 0.0,
        "coll_count": coll["count"],
        "coll_bytes_per_chip": coll_bytes,
        "hbm_gb_per_chip": (rec["memory"]["argument_bytes"]
                            + rec["memory"]["temp_bytes"]
                            + rec["memory"]["output_bytes"]
                            - rec["memory"]["alias_bytes"]) / 2**30,
    }


def load_all(mesh: str = "16x16") -> List[Dict]:
    rows = []
    for f in sorted(DRYRUN.glob(f"*__{mesh}.json")):
        rec = json.loads(f.read_text())
        row = analyze(rec)
        if row:
            rows.append(row)
    return rows


def print_table(rows: List[Dict], out=sys.stdout) -> None:
    cols = ["arch", "shape", "mesh", "source", "compute_s", "memory_s",
            "collective_s", "dominant", "roofline_frac", "useful_ratio",
            "hbm_gb_per_chip"]
    print(",".join(cols), file=out)
    for r in rows:
        vals = [f"{r[c]:.4g}" if isinstance(r[c], float) else str(r[c])
                for c in cols]
        print(",".join(vals), file=out)


def main() -> None:
    out_dir = ROOT / "results"
    out_dir.mkdir(exist_ok=True)
    all_rows = []
    for mesh in ("16x16", "2x16x16"):
        rows = load_all(mesh)
        all_rows.extend(rows)
    with open(out_dir / "roofline.csv", "w") as f:
        print_table(all_rows, f)
    print_table(all_rows)
    print(f"\n{len(all_rows)} cells analyzed -> results/roofline.csv",
          file=sys.stderr)


if __name__ == "__main__":
    main()
