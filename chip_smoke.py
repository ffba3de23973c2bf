#!/usr/bin/env python3
"""Bring-up smoke run: the simulator's vectorized engine and the live JAX
stack on a TPU, through the entry points a user calls.

    python3 chip_smoke.py                # one chip: phases (a)-(e)
    python3 chip_smoke.py --four-chips   # four chips: the sharded
                                         # trainer recovery, nothing else

Phases on one chip, one JSON line each:

  (a) device     — fail unless JAX's first device is a TPU.
  (b) vectorized — a 2048-chip ``ChipRingTraining`` job (the
      ``dist.sharded_large`` fleet size) on ``engine="vectorized"`` with
      the native ``minskew``/``hub_route`` kernels, identical to
      ``pallas="off"`` and to the host ``async`` engine; plus both
      kernels called directly against their jnp oracles at multi-block
      shapes.
  (c) sweep      — ``Simulation.sweep`` over 64 ``Straggler`` variants of
      that job; sampled lanes equal their solo runs and ``async``.
  (d) serve      — ``record_live_serve`` with the real ``BatchServer`` on
      qwen3-4b at its published widths and depth; cached decode logits
      agree with an uncached forward; the trace replays bit-exactly.
  (e) train      — ``TrainerStack`` on qwen3-4b at its published widths,
      depth cut to fit one chip; the loss is finite and parameters move.

``--four-chips`` runs ``record_live_recovery`` with the trainer meshed
over all four chips (data=4, re-meshed to data=2 after the FailHost),
against the same steps on one device, the placement of every parameter
leaf, and the async replay of the recorded trace.

Random weights come from ``--seed``.  Traces go under ``--out``
(default ``chip_smoke_out/``).  The last line of standard output is
``{"ok": true, "device": {...}}``; any failed check raises, so the exit
code is non-zero and that line is not printed.  The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when
it is set, and ``<repo>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: the fields ``tests/engine_harness.py::assert_vectorized_exact``
#: compares (its CORE_FIELDS, plus per-link stats between hub engines)
EXACT_FIELDS = ("status", "n_hosts", "vtime_ns", "messages", "bytes",
                "tasks", "progress", "cells", "live", "links")

ARCH = "qwen3_4b"
#: training depth on one 16 GB v5e chip.  memory_analysis of the v5e
#: compile of the full-width train step (batch 4, seq 256, bf16
#: params, f32 AdamW moments and gradient accumulator) gives 12.41 GiB
#: at 2 layers; 3 layers at batch 4, seq 512 need 16.69 GiB.
TRAIN_LAYERS = 2
TRAIN_SEQ, TRAIN_BATCH = 256, 4
SERVE_PROMPT, SERVE_DECODE, SERVE_BATCH, SERVE_REQUESTS = 512, 32, 4, 8


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def shown(path: pathlib.Path) -> str:
    return str(path.relative_to(ROOT) if path.is_relative_to(ROOT)
               else path)


# ---------------------------------------------------------------- (a)


def phase_device(need: int):
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) >= need, f"{need} chips needed, {len(devs)} present")
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs))
    return devs


# ---------------------------------------------------------------- (b)


def fleet_sim(scenario=None, *, n_pods=8, chips_per_pod=256, n_steps=4):
    """The 2048-chip data-parallel job (one vtask per chip)."""
    from repro.core.cluster import ClusterSpec, StepCost
    from repro.sim import ChipRingTraining, Scenario, Simulation, Topology
    wl = ChipRingTraining(
        ClusterSpec(n_pods=n_pods, chips_per_pod=chips_per_pod),
        StepCost(compute_ns=5_000_000, ici_bytes=50_000_000,
                 dcn_bytes=6_000_000), n_steps=n_steps)
    return Simulation(Topology.single_host(n_cpus=64), wl,
                      scenario or Scenario("baseline"))


def same_report(a, b, label: str) -> None:
    for f in EXACT_FIELDS:
        check(getattr(a, f) == getattr(b, f),
              f"{label}: {a.mode} vs {b.mode} differ on {f}")


def kernels_vs_oracles(seed: int, *, n=2148, s=300, m=8224, links=97):
    """Both kernels, natively, against their jnp oracles at shapes
    spanning several blocks (N > 512, S > 128) and tiles (M > 2048)."""
    from repro.core.engine_jax import (eligibility, hub_visibility,
                                       scope_minima)
    from repro.kernels.hub_route import hub_route
    from repro.kernels.minskew import minskew
    rng = np.random.default_rng(seed)
    vtime = jnp.asarray(rng.integers(0, 10_000, n), jnp.int32)
    runnable = jnp.asarray(rng.random(n) < 0.7)
    member = jnp.asarray(rng.random((n, s)) < 0.3)
    skew = jnp.asarray(rng.integers(1, 500, s), jnp.int32)
    minima, elig = minskew(vtime, runnable.astype(jnp.int8),
                           member.astype(jnp.int8), skew)
    ref_min = scope_minima(vtime, runnable, member)
    ref_elig = eligibility(vtime, runnable, member, skew, ref_min)
    check(np.array_equal(np.asarray(minima), np.asarray(ref_min)),
          "minskew minima differ from scope_minima")
    check(np.array_equal(np.asarray(elig) != 0, np.asarray(ref_elig)),
          "minskew eligibility differs from eligibility")

    link = np.sort(rng.integers(0, links, m)).astype(np.int32)
    send = np.zeros(m, np.int64)
    for lk in range(links):
        idx = np.flatnonzero(link == lk)
        send[idx] = np.sort(rng.integers(0, 1_000_000, idx.size))
    ser = jnp.asarray(rng.integers(0, 10_000, m), jnp.int32)
    args = (jnp.asarray(send, jnp.int32), jnp.ones(m, jnp.int32),
            jnp.asarray(link), jnp.ones(links, jnp.float32),
            jnp.asarray(rng.integers(0, 5_000, links), jnp.int32))
    vis = hub_route(*args, ser_ns=ser)
    check(np.array_equal(np.asarray(vis),
                         np.asarray(hub_visibility(*args, ser_ns=ser))),
          "hub_route differs from hub_visibility")
    return {"minskew_nxs": [n, s], "hub_route_m": m}


def phase_vectorized(seed: int) -> None:
    from repro.sim.vectorized import _resolve_pallas, compile_simulation
    check(_resolve_pallas("auto") == (True, False),
          "pallas='auto' did not resolve to the native kernels")
    comp = compile_simulation(fleet_sim())
    first = fleet_sim().run(engine="vectorized", verify=True)
    steady = fleet_sim().run(engine="vectorized", verify=True)
    off = fleet_sim().run(engine="vectorized", pallas="off")
    ref = fleet_sim().run(engine="async")
    for rep in (first, steady, off):
        same_report(ref, rep, "vectorized")
    check(first.status == "ok" and first.tier == "exact",
          f"status {first.status}, tier {first.tier}")
    emit("vectorized", tape=list(comp.tape.op_kind.shape),
         messages=first.messages, scopes=comp.tape.membership.shape[1],
         vtime_ns=first.vtime_ns, rounds=first.sync_rounds,
         first_call_s=first.wall_s, steady_call_s=steady.wall_s,
         compile_s=first.wall_s - steady.wall_s,
         pallas_off_first_call_s=off.wall_s, async_run_s=ref.wall_s,
         identical=["native", "off", "async"],
         kernels=kernels_vs_oracles(seed))


# ---------------------------------------------------------------- (c)


def phase_sweep(n_variants: int = 64, lanes=(0, 1, 37, 63)) -> None:
    from repro.sim import Scenario, Straggler
    axis = [Scenario(f"s{i}", (Straggler(f"chip{(i * 31) % 2048}",
                                         1.0 + 0.25 * (i % 8)),))
            for i in range(n_variants)]
    first = fleet_sim().sweep(axis)
    steady = fleet_sim().sweep(axis)
    check(first.tier == "exact", f"sweep tier {first.tier}")
    for i in lanes:
        solo = fleet_sim(axis[i]).run(engine="vectorized")
        d1, d2 = steady.reports[i].to_dict(), solo.to_dict()
        d1["wall_s"] = d2["wall_s"] = 0.0
        check(d1 == d2, f"sweep lane {i} differs from its solo run")
        ref = fleet_sim(axis[i]).run(engine="async")
        check(steady.reports[i].vtime_ns == ref.vtime_ns
              and steady.reports[i].tasks == ref.tasks,
              f"sweep lane {i} differs from async")
    emit("sweep", variants=n_variants, lanes_checked=list(lanes),
         first_call_s=first.wall_s, steady_call_s=steady.wall_s,
         steady_configs_per_s=steady.configs_per_s,
         vtime_ns=sorted({r.vtime_ns for r in steady.reports}))


# ---------------------------------------------------------------- (d)


def decode_matches_forward(stack, decode: int):
    """Greedy prefill + ``decode`` cached steps on the server's wave-0
    prompts, against one uncached ``registry.forward`` of request 0's
    prompt plus its generated tokens.  Returns (relative RMS error,
    max error over max |logit|)."""
    from repro.models import registry
    srv = stack.server
    prompts = stack._prompts(0)
    logits, cache = srv._prefill(srv.params, prompts, None)
    seen = [logits[0]]
    toks = []
    for _ in range(decode):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(tok[0])
        logits, cache = srv._decode(srv.params, tok, cache)
        seen.append(logits[0])
    cached = np.asarray(jnp.stack(seen), np.float32)
    seq = jnp.concatenate([prompts[0], jnp.stack(toks)])[None]
    full = jax.jit(lambda p, t: registry.forward(srv.cfg, p, t))(
        srv.params, seq)
    ref = np.asarray(full[0, prompts.shape[1] - 1:], np.float32)
    rms = float(np.sqrt(np.mean((cached - ref) ** 2))
                / np.sqrt(np.mean(ref ** 2)))
    peak = float(np.max(np.abs(cached - ref)) / np.max(np.abs(ref)))
    return rms, peak


def phase_serve(out: pathlib.Path, cfg, *, prompt_len=SERVE_PROMPT,
                decode=SERVE_DECODE, batch=SERVE_BATCH,
                n_requests=SERVE_REQUESTS, seed=0) -> None:
    from repro.live import CostLedger
    from repro.sim.live import (ServeStack, live_serve_sim,
                                record_live_serve, serve_latency)
    stack = ServeStack(cfg=cfg, max_batch=batch, prompt_len=prompt_len,
                       decode_steps=decode, seed=seed)
    t0 = time.perf_counter()
    stack.setup()
    setup_s = time.perf_counter() - t0
    trace = out / "serve_trace.json"
    report, ledger = record_live_serve(
        trace, stack=stack, n_requests=n_requests, max_batch=batch,
        decode_steps=decode, seed=seed)
    check(report.status == "ok", report.detail)
    replay = live_serve_sim(CostLedger.replay(trace)).run(engine="async")
    check(replay.vtime_ns == report.vtime_ns
          and serve_latency(replay) == serve_latency(report),
          "serve replay differs from the recording")
    rms, peak = decode_matches_forward(stack, decode)
    # bf16 weights and activations (eps 2**-8) through 36 layers, and
    # cached vs full-sequence attention sum in different orders: a few
    # percent; a wrong cache position or mask is off by O(1)
    check(rms < 0.05 and peak < 0.05,
          f"cached decode vs forward: rel rms {rms}, rel max {peak}")
    emit("serve", model=cfg.name, layers=cfg.n_layers,
         d_model=cfg.d_model, vocab=cfg.vocab, prompt_len=prompt_len,
         decode_steps=decode, max_batch=batch, requests=n_requests,
         setup_compile_s=setup_s, vtime_ns=report.vtime_ns,
         latency_ns=serve_latency(report), recorded_costs=len(ledger.tasks["serve.live"]),
         replay_vtime_ns=replay.vtime_ns,
         decode_vs_forward_rel_rms=rms, decode_vs_forward_rel_max=peak,
         trace=shown(trace))
    stack.close()


# ---------------------------------------------------------------- (e)


def train_config(layers: int = TRAIN_LAYERS):
    from repro import configs
    return dataclasses.replace(configs.get(ARCH), n_layers=layers)


def host_copy(params):
    """Small host copies of a few leaves, to see them move."""
    return [np.asarray(params["final_norm"], np.float32),
            np.asarray(params["lm_head"][:, :64], np.float32),
            np.asarray(params["layers"]["ln1"], np.float32)]


def phase_train(cfg, full_layers: int, *, seq_len=TRAIN_SEQ,
                batch=TRAIN_BATCH, steps=3, seed=0) -> None:
    from repro.sim.live import TrainerStack
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        stack = TrainerStack(cfg=cfg, n_steps=steps + 1, seq_len=seq_len,
                             global_batch=batch, mesh_shape=(1, 1),
                             checkpoint_dir=ckpt, seed=seed)
        t0 = time.perf_counter()
        stack.setup()
        setup_s = time.perf_counter() - t0
        tr = stack.trainer
        mem = tr.step.lower(stack.params, stack.opt, jnp.int32(0),
                            tr.data.batch(0)).compile().memory_analysis()
        before = host_copy(stack.params)
        walls = []
        for s in range(1, steps + 1):
            t0 = time.perf_counter()
            stack.step(s)
            walls.append(time.perf_counter() - t0)
        after = host_copy(stack.params)
        losses = [loss for _, loss in stack.history]
        check(all(math.isfinite(x) for x in losses), f"loss {losses}")
        check(all(not np.array_equal(a, b) for a, b in zip(before, after)),
              "parameters did not change")
        stats = jax.devices()[0].memory_stats() or {}
        stack.close()
    gib = 2.0 ** 30
    emit("train", model=cfg.name, depth_cut=f"{full_layers}->{cfg.n_layers}",
         d_model=cfg.d_model, d_ff=cfg.d_ff, vocab=cfg.vocab,
         seq_len=seq_len, global_batch=batch, losses=losses,
         step_wall_s=walls, setup_compile_s=setup_s,
         compiled_step_gib=(mem.argument_size_in_bytes
                            + mem.output_size_in_bytes
                            - mem.alias_size_in_bytes
                            + mem.temp_size_in_bytes) / gib,
         device_limit_gib=stats.get("bytes_limit", 0) / gib,
         device_peak_gib=stats.get("peak_bytes_in_use", 0) / gib)


# ---------------------------------------------------------- four chips


def phase_four_chips(out: pathlib.Path, cfg, *, seq_len=TRAIN_SEQ,
                     batch=TRAIN_BATCH, seed=0) -> None:
    """Sharded recovery over 4 chips vs. the same steps on one device."""
    from repro.live import CostLedger
    from repro.sim.live import (TrainerStack, live_recovery_sim,
                                record_live_recovery, recovery_timeline)
    n_steps, every = 4, 2
    devices = set(jax.devices()[:4])
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        # one-device reference first: its state must leave device 0
        # before the 4-way stack arrives
        one = TrainerStack(cfg=cfg, n_steps=n_steps, seq_len=seq_len,
                           global_batch=batch, mesh_shape=(1, 1),
                           checkpoint_dir=f"{ckpt}/one", seed=seed)
        one.setup()
        for s in (0, 0, 1, 2):          # probe step, then the run
            one.step(s)
        ref_losses = [loss for _, loss in one.history]
        one.close()
        del one
        gc.collect()

        stack = TrainerStack(cfg=cfg, n_steps=n_steps, seq_len=seq_len,
                             global_batch=batch, mesh_shape=(4, 1),
                             remesh_shape=(2, 1),
                             checkpoint_dir=f"{ckpt}/four", seed=seed)
        stack.setup()
        leaves = jax.tree.leaves(stack.params)
        check(all(x.sharding.device_set == devices for x in leaves),
              "a parameter leaf is not on all 4 devices")
        sharded = sum(not x.sharding.is_fully_replicated for x in leaves)
        check(sharded > 0, "no parameter leaf is sharded")
        trace = out / "recovery_trace.json"
        report, _ = record_live_recovery(trace, stack=stack,
                                         n_steps=n_steps,
                                         checkpoint_every=every)
        check(report.status == "ok", report.detail)
        timeline = recovery_timeline(report)
        check([e["event"] for e in timeline]
              == ["detect", "restore", "remesh", "resumed"],
              f"recovery timeline {timeline}")
        four_losses = [loss for _, loss in stack.history]
        pre = four_losses[:len(ref_losses)]
        check(np.allclose(pre, ref_losses, rtol=1e-2, atol=0),
              f"4-chip losses {pre} vs 1-device {ref_losses}")
        after = set(jax.tree.leaves(stack.params)[0].sharding.device_set)
        replay = live_recovery_sim(CostLedger.replay(trace)).run(
            engine="async")
        check(replay.vtime_ns == report.vtime_ns
              and replay.tasks == report.tasks
              and recovery_timeline(replay) == timeline,
              "recovery replay differs from the recording")
    emit("four_chips", model=cfg.name, layers=cfg.n_layers,
         mesh="data=4 -> data=2", leaves=len(leaves),
         sharded_leaves=sharded, devices_before=len(devices),
         devices_after=len(after), losses_4chip=four_losses,
         losses_1device=ref_losses, timeline=timeline,
         vtime_ns=report.vtime_ns, replay_vtime_ns=replay.vtime_ns,
         trace=shown(trace))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded recovery")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"))
    args = ap.parse_args(argv)
    from repro import configs
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    need = 4 if args.four_chips else 1
    devs = phase_device(need)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    full_layers = configs.get(ARCH).n_layers
    if args.four_chips:
        phase_four_chips(out, train_config(), seed=args.seed)
    else:
        phase_vectorized(args.seed)
        phase_sweep()
        phase_serve(out, configs.get(ARCH), seed=args.seed)
        gc.collect()
        phase_train(train_config(), full_layers, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
