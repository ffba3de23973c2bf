import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Corrected cost counting for the roofline (§Dry-run methodology).
#
# XLA's cost_analysis counts while-loop bodies ONCE, not x trip-count
# (verified: a scanned 8-layer stack reports exactly 1/8 of the unrolled
# flops).  The full-config dry-run therefore proves compilability and
# memory, while THIS module produces the corrected per-chip flops/bytes/
# collective-bytes used in the roofline:
#
#   1. compile small "counting" variants with every inner loop unrolled
#      (chunked attention, mLSTM chunks, microbatch accumulation, layer
#      stacks — via cfg.scan_layers=False + the unroll context),
#   2. at several layer counts per kind (dense: L in {1,2}; rglru:
#      {1,3,6} solving (base, rec, attn); xlstm: {(1,0),(2,2),(4,4)}
#      layers x slstm_every solving (base, mlstm, slstm); encdec scales
#      enc/dec separately) — always at the production n_mb (totals are
#      n_mb-independent: same tokens; verified <2% on design points),
#   3. solve the linear attribution  cost = base + sum_k n_k * kind_k
#      and evaluate at the production counts,
#   4. add the analytic correction for the sLSTM time scan (its per-step
#      body is counted once but runs S times; the body cost is closed
#      form: 4 recurrent (H, hd, hd) matmuls + elementwise gates).
#
# Results land in results/costs/<arch>__<shape>__<mesh>.json.

import argparse
import dataclasses
import json
import pathlib
import sys
import traceback

import numpy as np

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "costs"


def _counting_cfg(cfg, n_layers, n_enc=None, **extra):
    kw = dict(n_layers=n_layers, scan_layers=False, **extra)
    if n_enc is not None:
        kw["n_enc_layers"] = n_enc
    return dataclasses.replace(cfg, **kw)


def _kind_counts(cfg):
    """Per-kind layer counts for the attribution model."""
    if cfg.family == "rglru":
        from repro.models.rglru import _counts

        r, a = _counts(cfg)
        return {"rec": r, "attn": a}
    if cfg.family == "xlstm":
        from repro.models.xlstm import _block_ids

        m, s = _block_ids(cfg)
        return {"mlstm": len(m), "slstm": len(s)}
    if cfg.family == "encdec":
        return {"enc": cfg.n_enc_layers or cfg.n_layers,
                "dec": cfg.n_layers}
    return {"layer": cfg.n_layers}


def _design_points(cfg):
    """Counting configs: list of (cfg_variant, kind_counts dict)."""
    if cfg.family == "rglru":
        ls = [1, 3, 6]
    elif cfg.family == "xlstm":
        # small but identifiable (mlstm, slstm) counts: (1,0),(1,1),(3,1)
        pts = []
        for nl, se in [(1, 0), (2, 2), (4, 4)]:
            c = _counting_cfg(cfg, nl, slstm_every=se)
            pts.append((c, _kind_counts(c)))
        return pts
    elif cfg.family == "encdec":
        pts = []
        for ne, nd in [(1, 1), (2, 1), (1, 2)]:
            c = _counting_cfg(cfg, nd, n_enc=ne)
            pts.append((c, _kind_counts(c)))
        return pts
    else:
        ls = [1, 2]
    pts = []
    for l in ls:
        c = _counting_cfg(cfg, l)
        pts.append((c, _kind_counts(c)))
    return pts


def _measure(cfg, shape, mesh, n_mb):
    """Compile one counting variant; return dict of metrics."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import dryrun as dr
    from repro.launch import shapes as shp
    from repro.models import registry
    from repro.optim import opt_state_specs
    from repro.parallel import ctx as pctx
    from repro.parallel import sharding as shd
    from repro.serve.step import (build_decode_step, build_prefill_step,
                                  cache_shardings, serve_rules)
    from repro.train.step import build_train_step, train_state_shardings

    ispecs = shp.input_specs(cfg, shape)
    with pctx.use_mesh(mesh), pctx.use_unroll(True):
        if shape.kind == "train":
            step = build_train_step(cfg, n_microbatch=n_mb)
            p_sh, o_sh = train_state_shardings(cfg, mesh)
            p_specs = registry.param_specs(cfg)
            o_specs = opt_state_specs(p_specs)
            b_sh = {k: shd.batch_sharding(mesh, len(v.shape))
                    for k, v in ispecs.items()}
            fn = jax.jit(step,
                         in_shardings=(p_sh, o_sh, NamedSharding(mesh, P()),
                                       b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(p_specs, o_specs,
                               jax.ShapeDtypeStruct((), jnp.int32), ispecs)
        elif shape.kind == "prefill":
            step = build_prefill_step(cfg)
            rules = serve_rules(cfg, mesh, shape.batch)
            p_specs = registry.param_specs(cfg)
            p_sh = shd.shardings_from_axes(registry.logical_axes(cfg),
                                           mesh, rules, p_specs)
            c_sh = cache_shardings(cfg, mesh, shape.batch, shape.seq + 64,
                                   rules)
            b_sh = {k: shd.batch_sharding(mesh, len(v.shape))
                    for k, v in ispecs.items()}
            logits_sh = NamedSharding(mesh, shd.spec_from_axes(
                ("batch", "vocab"), mesh, rules, (shape.batch, cfg.vocab)))
            if "frontend_embeds" in ispecs:
                fn = jax.jit(step, in_shardings=(
                    p_sh, b_sh["tokens"], b_sh["frontend_embeds"]),
                    out_shardings=(logits_sh, c_sh))
                lowered = fn.lower(p_specs, ispecs["tokens"],
                                   ispecs["frontend_embeds"])
            else:
                fn = jax.jit(step, in_shardings=(p_sh, b_sh["tokens"]),
                             out_shardings=(logits_sh, c_sh))
                lowered = fn.lower(p_specs, ispecs["tokens"])
        else:
            step = build_decode_step(cfg)
            rules = serve_rules(cfg, mesh, shape.batch)
            p_specs = registry.param_specs(cfg)
            p_sh = shd.shardings_from_axes(registry.logical_axes(cfg),
                                           mesh, rules, p_specs)
            c_sh = cache_shardings(cfg, mesh, shape.batch, shape.seq,
                                   rules)
            tok_sh = NamedSharding(mesh, shd.spec_from_axes(
                ("batch",), mesh, rules, (shape.batch,)))
            logits_sh = NamedSharding(mesh, shd.spec_from_axes(
                ("batch", "vocab"), mesh, rules, (shape.batch, cfg.vocab)))
            fn = jax.jit(step, in_shardings=(p_sh, tok_sh, c_sh),
                         out_shardings=(logits_sh, c_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(p_specs, ispecs["token"], ispecs["cache"])
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = dr.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(sum(v for k, v in coll.items()
                                if k != "count")),
        "coll_count": float(coll["count"]),
    }


def _slstm_analytic(cfg, shape, mesh):
    """Per-chip correction for the sLSTM time scan (counted once,
    runs S times): (S-1) x per-step body, per sLSTM layer."""
    if cfg.family != "xlstm":
        return {}
    from repro.models.xlstm import _block_ids
    from repro.parallel import ctx as pctx

    _, s_ids = _block_ids(cfg)
    n_slstm = len(s_ids)
    if n_slstm == 0:
        return {}
    if shape.kind == "decode":
        return {}                       # S == 1 at decode
    seq = shape.seq
    dp = pctx.dp_size(mesh)
    b_loc = max(shape.batch // dp, 1)
    h, hd = cfg.n_heads, cfg.d_model // cfg.n_heads
    # per-step: 4 recurrent einsums (B,H,hd)x(H,hd,hd) + ~12 elementwise
    flops_step = b_loc * (4 * h * hd * hd * 2 + 12 * h * hd)
    bytes_step = 4 * h * hd * hd * 4 + b_loc * h * hd * 4 * 10
    mult = n_slstm * (seq - 1)
    if shape.kind == "train":
        mult *= 3                       # fwd + remat-fwd + bwd
    return {"flops": flops_step * mult, "bytes": bytes_step * mult,
            "coll_bytes": 0.0, "coll_count": 0.0}


def corrected_costs(arch: str, shape_name: str, multi_pod: bool) -> dict:
    from repro import configs
    from repro.launch import shapes as shp
    from repro.launch.mesh import make_production_mesh

    cfg = configs.get(arch)
    shape = shp.SHAPES[shape_name]
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    skip = shp.applicable(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                "status": "n/a", "reason": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    # Total flops/bytes/collective-bytes are independent of the
    # gradient-accumulation split (same tokens, weight collectives hoisted
    # once per step), verified to <2% on the design points — so counting
    # runs at the production n_mb (unrolled) and the fit is over layer
    # counts only.
    n_mb_real = (shp.MICROBATCH.get(arch, 1) if shape.kind == "train"
                 else 1)

    pts = _design_points(cfg)
    kinds = sorted(_kind_counts(cfg))
    metrics = ["flops", "bytes", "coll_bytes", "coll_count"]

    rows, feats = [], []
    for c_var, counts in pts:
        m = _measure(c_var, shape, mesh, n_mb_real)
        rows.append([m[k] for k in metrics])
        feats.append([1.0] + [float(counts[k]) for k in kinds])
    A = np.asarray(feats)
    Y = np.asarray(rows)
    coef, *_ = np.linalg.lstsq(A, Y, rcond=None)

    # evaluate at production counts
    counts_real = _kind_counts(cfg)
    f = [1.0] + [float(counts_real[k]) for k in kinds]
    pred = np.asarray(f) @ coef
    result = dict(zip(metrics, [float(max(v, 0.0)) for v in pred]))

    extra = _slstm_analytic(cfg, shape, mesh)
    for k, v in extra.items():
        result[k] = result.get(k, 0.0) + v

    out = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "status": "ok", "n_chips": int(mesh.devices.size),
           "corrected": result,
           "design_points": [dict(zip(metrics, r)) for r in rows]}
    return out


def run_cell(arch, shape_name, multi_pod, verbose=True):
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    try:
        res = corrected_costs(arch, shape_name, multi_pod)
    except Exception as e:  # noqa: BLE001
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"{arch}__{shape_name}__{mesh_tag}.json").write_text(
        json.dumps(res, indent=2))
    if verbose:
        if res["status"] == "ok":
            c = res["corrected"]
            print(f"[ok] {arch} x {shape_name} x {mesh_tag}: "
                  f"flops/chip={c['flops']:.3e} bytes/chip={c['bytes']:.3e}"
                  f" coll/chip={c['coll_bytes']:.3e}")
        else:
            print(f"[{res['status']}] {arch} x {shape_name} x {mesh_tag}: "
                  f"{res.get('reason', res.get('error',''))[:300]}")
    return res


def main(argv=None):
    from repro import configs
    from repro.launch import shapes as shp

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)
    archs = [args.arch] if args.arch else configs.ARCHS
    shapes = [args.shape] if args.shape else list(shp.SHAPES)
    fails = 0
    for a in archs:
        for s in shapes:
            tag = "2x16x16" if args.multi_pod else "16x16"
            f = RESULTS / f"{a}__{s}__{tag}.json"
            if args.skip_existing and f.exists():
                prev = json.loads(f.read_text())
                if prev.get("status") in ("ok", "n/a"):
                    continue
            r = run_cell(a, s, args.multi_pod)
            fails += r["status"] == "error"
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    main()
