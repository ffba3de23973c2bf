"""Vectorized fast-path engine: LiveStack's "keep vtime updates and IPC
delivery on the kernel hot path" principle, realized as compiled JAX.

The reference scheduler dispatches Python generators — perfect semantics,
O(n) Python per round.  Cluster-scale simulations (one vtask per chip at
512..100k chips) need the hot path compiled.  This engine vectorizes the
scheduler inner loop over ALL vtasks as array ops under ``jax.jit``:

  state arrays:  vtime (N,) int64, runnable (N,) bool,
                 scope membership M (N, S) bool
  per round:     scope minima  -> eligibility mask (bounded skew)
                 -> advance eligible vtasks by their per-dispatch duration
                 -> message visibility + delivery counts

The per-round math matches ``Scheduler`` exactly for compute-only vtasks
(property-tested against it), and is the substrate for the cluster
simulations in ``repro.core.cluster``.  The segmented-min/eligibility hot
spot has a Pallas TPU kernel (``repro.kernels.minskew``); the jnp path
here is its oracle and CPU fallback.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INF = jnp.int32(2**30)
INF_TICKS = 2**30               # python-int mirror of INF
TICK_NS = 100  # cluster sims use 0.1us ticks: int32 range = ~214 simulated s


class TickRangeError(ValueError):
    """Simulated times would overflow the engine's int32 tick range
    (``INF = 2**30`` ticks).  Raised at build time — before any round
    runs — so an over-long horizon is an explicit error instead of a
    silent int32 wraparound mid-simulation.  Fix: fewer steps / shorter
    durations, or a coarser tick (``TICK_NS`` for the synthetic engine,
    ``tick_ns=`` for the facade compiler)."""


@dataclasses.dataclass
class VecState:
    """Array-of-structs state for N vtasks / S scopes."""
    vtime: jnp.ndarray          # (N,) int32 ticks
    runnable: jnp.ndarray       # (N,) bool
    membership: jnp.ndarray     # (N, S) bool
    skew: jnp.ndarray           # (S,) int32
    duration: jnp.ndarray       # (N,) int32 — per-dispatch vtime advance
    steps_left: jnp.ndarray     # (N,) int32 — dispatches until done

    @staticmethod
    def create(n: int, scopes: int, durations, steps, membership, skews):
        durations = np.asarray(durations, np.int64).reshape(n)
        steps = np.asarray(steps, np.int64).reshape(n)
        if (durations < 0).any() or (steps < 0).any():
            raise ValueError("durations and steps must be >= 0")
        # per-task final vtime = duration * steps, exactly (vtime only
        # advances by own durations); validate it fits the tick range
        # instead of wrapping int32 mid-run
        total = durations * steps
        if total.size and int(total.max()) >= INF_TICKS:
            worst = int(np.argmax(total))
            raise TickRangeError(
                f"vtask {worst}: duration {int(durations[worst])} x "
                f"steps {int(steps[worst])} = {int(total[worst])} ticks "
                f">= 2**30 — exceeds the int32 tick range; use a "
                f"coarser tick (TICK_NS) or fewer steps")
        return VecState(
            vtime=jnp.zeros((n,), jnp.int32),
            runnable=jnp.asarray(steps > 0),
            membership=jnp.asarray(membership, bool).reshape(n, scopes),
            skew=jnp.asarray(skews, jnp.int32).reshape(scopes),
            duration=jnp.asarray(durations, jnp.int32).reshape(n),
            steps_left=jnp.asarray(steps, jnp.int32).reshape(n),
        )


def scope_minima(vtime: jnp.ndarray, runnable: jnp.ndarray,
                 membership: jnp.ndarray) -> jnp.ndarray:
    """(S,) min vtime over runnable members (INF when none) — the cached
    scope vtime of the paper, recomputed batch-style."""
    v = jnp.where(runnable[:, None] & membership, vtime[:, None], INF)
    return jnp.min(v, axis=0)


def eligibility(vtime: jnp.ndarray, runnable: jnp.ndarray,
                membership: jnp.ndarray, skew: jnp.ndarray,
                minima: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Bounded-skew dispatch mask: eligible iff for EVERY scope the vtask
    belongs to, vtime <= scope_min + skew."""
    if minima is None:
        minima = scope_minima(vtime, runnable, membership)
    ok_scope = vtime[:, None] <= minima[None, :] + skew[None, :]
    ok = jnp.all(ok_scope | ~membership | (minima == INF)[None, :], axis=1)
    return ok & runnable


@partial(jax.jit, donate_argnums=(0,))
def _round(state: VecState) -> VecState:
    minima = scope_minima(state.vtime, state.runnable, state.membership)
    elig = eligibility(state.vtime, state.runnable, state.membership,
                       state.skew, minima)
    vtime = jnp.where(elig, state.vtime + state.duration, state.vtime)
    steps = jnp.where(elig, state.steps_left - 1, state.steps_left)
    runnable = state.runnable & (steps > 0)
    return dataclasses.replace(state, vtime=vtime, runnable=runnable,
                               steps_left=steps)


jax.tree_util.register_dataclass(
    VecState,
    data_fields=["vtime", "runnable", "membership", "skew", "duration",
                 "steps_left"],
    meta_fields=[])


@jax.jit
def _run_rounds(state: VecState, max_rounds):
    def cond(carry):
        st, i = carry
        return jnp.any(st.runnable) & (i < max_rounds)

    def body(carry):
        st, i = carry
        return _round(st), i + 1

    return jax.lax.while_loop(cond, body, (state, jnp.int32(0)))


def run_vectorized(state: VecState, max_rounds: int = 1_000_000
                   ) -> Tuple[VecState, int]:
    """Run rounds until no vtask is runnable.  Uses a compiled while loop
    (whole simulation stays on device — zero Python per round), jitted
    once per state shape: an unjitted loop would be traced and compiled
    again on every call."""
    st, rounds = _run_rounds(state, max_rounds)
    return st, int(rounds)


@partial(jax.jit, static_argnums=(2,))
def _sweep_one(state: VecState, durations: jnp.ndarray,
               max_rounds: int):
    st = dataclasses.replace(state, duration=durations)

    def cond(carry):
        s, i = carry
        return jnp.any(s.runnable) & (i < max_rounds)

    def body(carry):
        s, i = carry
        minima = scope_minima(s.vtime, s.runnable, s.membership)
        elig = eligibility(s.vtime, s.runnable, s.membership, s.skew,
                           minima)
        vtime = jnp.where(elig, s.vtime + s.duration, s.vtime)
        steps = jnp.where(elig, s.steps_left - 1, s.steps_left)
        runnable = s.runnable & (steps > 0)
        return (dataclasses.replace(s, vtime=vtime, runnable=runnable,
                                    steps_left=steps), i + 1)

    st, rounds = jax.lax.while_loop(cond, body, (st, jnp.int32(0)))
    return st.vtime, rounds


def run_vectorized_sweep(state: VecState, duration_axis,
                         max_rounds: int = 1_000_000):
    """Batched configuration sweep: ``jax.vmap`` the whole while-loop
    simulation over a (V, N) axis of per-task durations (V config
    variants sharing everything else).  Returns (final vtimes (V, N),
    rounds (V,)) — V simulations for one compiled dispatch."""
    duration_axis = jnp.asarray(duration_axis, jnp.int32)
    vt, rounds = jax.vmap(_sweep_one, in_axes=(None, 0, None))(
        state, duration_axis, max_rounds)
    return vt, rounds


# ---------------------------------------------------------------------------
# Facade tape interpreter (`Simulation.run(engine="vectorized")`)
# ---------------------------------------------------------------------------
#
# The facade compiler (``repro.sim.vectorized``) lowers a scenario to a
# static per-task *op tape* plus per-message routing tables; this module
# owns the jitted round loop that interprets the tapes.  Per round, for
# every non-done task: fail gates fire, the current op's readiness and
# bounded-skew eligibility are evaluated (the minskew Pallas kernel or
# the jnp oracle above), and eligible tasks execute exactly one op.  On
# the scenario surface the compiler admits, results are provably
# schedule-independent, so this loop is bit-identical to the reference
# engines (see tests/engine_harness.py).

OP_END, OP_COMPUTE, OP_SEND, OP_RECV = 0, 1, 2, 3


@dataclasses.dataclass
class VecTape:
    """Static (per-compile) arrays: tapes, scopes, message routing."""
    op_kind: jnp.ndarray        # (N, P) int32: OP_*
    op_arg: jnp.ndarray         # (N, P) int32: ticks | message id
    n_ops: jnp.ndarray          # (N,) int32
    fail_pc: jnp.ndarray        # (N,) int32 (INF = never)
    fail_vtime: jnp.ndarray     # (N,) int32 ticks (INF = never)
    membership: jnp.ndarray     # (N, S) bool
    skew: jnp.ndarray           # (S,) int32 ticks
    send_overhead: jnp.ndarray  # () int32 ticks
    msg_ch1: jnp.ndarray        # (M,) int32 — stage-1 channel
    msg_ser1: jnp.ndarray       # (M,) int32 ticks
    msg_lat1: jnp.ndarray       # (M,) int32 ticks
    msg_two_stage: jnp.ndarray  # (M,) bool — cross-host second hop
    msg_ch2: jnp.ndarray        # (M,) int32
    msg_ser2: jnp.ndarray       # (M,) int32 ticks
    msg_lat2: jnp.ndarray       # (M,) int32 ticks
    msg_extra: jnp.ndarray      # (M, D) int32 — DegradeLink extras
    msg_extra_from: jnp.ndarray  # (M, D) int32 — send_vtime thresholds


@dataclasses.dataclass
class VecSimState:
    """Per-round mutable state.  ``sent``/``vis``/``sent_vt`` carry one
    extra trailing row — the unmatched-recv sentinel (never sent, so a
    receiver matched to it blocks forever, as in the reference)."""
    vtime: jnp.ndarray          # (N,) int32 ticks
    pc: jnp.ndarray             # (N,) int32
    done: jnp.ndarray           # (N,) bool
    sent: jnp.ndarray           # (M+1,) bool
    vis: jnp.ndarray            # (M+1,) int32 — final visibility
    sent_vt: jnp.ndarray        # (M+1,) int32 — send vtime (overhead incl.)
    busy: jnp.ndarray           # (C,) int32 — per-channel busy-until
    rounds: jnp.ndarray         # () int32
    progressed: jnp.ndarray     # () bool — any op executed / kill fired


for _cls, _fields in ((VecTape, ["op_kind", "op_arg", "n_ops", "fail_pc",
                                 "fail_vtime", "membership", "skew",
                                 "send_overhead", "msg_ch1", "msg_ser1",
                                 "msg_lat1", "msg_two_stage", "msg_ch2",
                                 "msg_ser2", "msg_lat2", "msg_extra",
                                 "msg_extra_from"]),
                      (VecSimState, ["vtime", "pc", "done", "sent",
                                     "vis", "sent_vt", "busy", "rounds",
                                     "progressed"])):
    jax.tree_util.register_dataclass(_cls, data_fields=_fields,
                                     meta_fields=[])


def init_vec_sim_state(tape: VecTape, n_channels: int) -> VecSimState:
    n = tape.op_kind.shape[0]
    m1 = tape.msg_ch1.shape[0] + 1
    return VecSimState(
        vtime=jnp.zeros((n,), jnp.int32),
        pc=jnp.zeros((n,), jnp.int32),
        done=(tape.n_ops == 0),
        sent=jnp.zeros((m1,), bool),
        vis=jnp.zeros((m1,), jnp.int32),
        sent_vt=jnp.zeros((m1,), jnp.int32),
        busy=jnp.zeros((max(n_channels, 1),), jnp.int32),
        rounds=jnp.int32(0),
        progressed=jnp.asarray(True),
    )


def vec_sim_round(tape: VecTape, st: VecSimState, *,
                  pallas: bool = False,
                  interpret: bool = False) -> VecSimState:
    """One dispatch round.  Kill gates fire *before* execution (matching
    ``fail_gated_body``: the wrapped generator returns when the op at
    the fail boundary is produced, before it runs); blocked receivers
    are excluded from scope minima (reference: blocked vtasks leave the
    runnable heap); the effective vtime of a ready receiver is
    max(vtime, visibility) in both minima and eligibility (reference:
    ``scope.wake`` forwards vtime before the retry dispatch)."""
    n, p = tape.op_kind.shape
    m = tape.msg_ch1.shape[0]
    idx = jnp.arange(n)
    pcc = jnp.clip(st.pc, 0, max(p - 1, 0))
    kind = tape.op_kind[idx, pcc]
    arg = tape.op_arg[idx, pcc]

    active = ~st.done
    kill = active & ((st.pc == tape.fail_pc)
                     | (st.vtime >= tape.fail_vtime))
    active = active & ~kill
    done = st.done | kill

    is_recv = active & (kind == OP_RECV)
    marg = jnp.where(is_recv, arg, 0)
    recv_ready = is_recv & st.sent[marg]
    ready = active & (~is_recv | recv_ready)
    eff = jnp.where(recv_ready, jnp.maximum(st.vtime, st.vis[marg]),
                    st.vtime)

    if tape.membership.shape[1] == 0:
        elig = ready
    elif pallas:
        from repro.kernels.minskew import minskew
        _, elig8 = minskew(eff, ready.astype(jnp.int8),
                           tape.membership.astype(jnp.int8), tape.skew,
                           interpret=interpret)
        elig = elig8 != 0
    else:
        minima = scope_minima(eff, ready, tape.membership)
        elig = eligibility(eff, ready, tape.membership, tape.skew,
                           minima)

    do_comp = elig & (kind == OP_COMPUTE)
    do_send = elig & (kind == OP_SEND)
    do_recv = elig & (kind == OP_RECV)
    sv = st.vtime + tape.send_overhead
    vtime = jnp.where(do_comp, st.vtime + arg, st.vtime)
    vtime = jnp.where(do_recv, jnp.maximum(st.vtime, st.vis[marg]),
                      vtime)
    vtime = jnp.where(do_send, sv, vtime)

    # sends: at most one message per channel per round (single-producer
    # channels, one op per task per round), so plain scatters suffice
    m_idx = jnp.where(do_send, arg, m + 1)     # m+1 = out of range: drop
    sent_vt = st.sent_vt.at[m_idx].set(sv, mode="drop")
    sent = st.sent.at[m_idx].set(True, mode="drop")
    now = sent[:m] & ~st.sent[:m]              # newly sent this round
    msv = sent_vt[:m]
    start1 = jnp.maximum(msv, st.busy[tape.msg_ch1])
    end1 = start1 + tape.msg_ser1
    extra = jnp.sum(jnp.where(msv[:, None] >= tape.msg_extra_from,
                              tape.msg_extra, 0),
                    axis=1).astype(jnp.int32)
    vis1 = end1 + tape.msg_lat1 + extra        # extra is post-busy (hook)
    start2 = jnp.maximum(vis1, st.busy[tape.msg_ch2])
    end2 = start2 + tape.msg_ser2
    vis2 = end2 + tape.msg_lat2
    vism = jnp.where(tape.msg_two_stage, vis2, vis1)
    c = st.busy.shape[0]
    busy = st.busy.at[jnp.where(now, tape.msg_ch1, c)].set(
        end1, mode="drop")
    busy = busy.at[jnp.where(now & tape.msg_two_stage,
                             tape.msg_ch2, c)].set(end2, mode="drop")
    vis = st.vis.at[:m].set(jnp.where(now, vism, st.vis[:m]))

    pc = jnp.where(elig, st.pc + 1, st.pc)
    done = done | (pc >= tape.n_ops)
    return VecSimState(
        vtime=vtime, pc=pc, done=done, sent=sent, vis=vis,
        sent_vt=sent_vt, busy=busy, rounds=st.rounds + 1,
        progressed=jnp.any(elig) | jnp.any(kill))


@partial(jax.jit, static_argnames=("pallas", "interpret"))
def run_vec_tape(tape: VecTape, st: VecSimState, max_rounds,
                 *, pallas: bool = False,
                 interpret: bool = False) -> VecSimState:
    """Run rounds to the fixpoint: every task done, or no op executed
    and no kill fired (the remaining tasks are blocked — a deadlock).
    Whole run stays on device; the minimal ready task is always
    eligible, so each round progresses and rounds <= total ops + N."""

    def cond(s):
        return (jnp.any(~s.done) & s.progressed
                & (s.rounds < max_rounds))

    def body(s):
        return vec_sim_round(tape, s, pallas=pallas, interpret=interpret)

    return jax.lax.while_loop(cond, body, st)


def run_vec_tape_batch(tapes: VecTape, states: VecSimState,
                       max_rounds) -> VecSimState:
    """vmap the whole tape interpreter over a leading variants axis
    (every leaf of ``tapes``/``states`` stacked to (V, ...)).  The
    batched while-loop masks finished variants, so per-variant results
    are identical to running each tape alone (asserted in tests).  Uses
    the jnp eligibility path — the Pallas kernel serves single runs."""
    return jax.vmap(
        lambda t, s: run_vec_tape(t, s, max_rounds))(tapes, states)


# ---------------------------------------------------------------------------
# Batched IPC visibility (hub fast path)
# ---------------------------------------------------------------------------


@jax.jit
def hub_visibility(send_vtime: jnp.ndarray, size_bytes: jnp.ndarray,
                   link_id: jnp.ndarray, link_bw_Bps: jnp.ndarray,
                   link_lat_ns: jnp.ndarray,
                   ser_ns: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Visibility times for a batch of messages with FIFO link queuing.

    Messages must be sorted by (link_id, send_vtime).  Per link:
      start_i = max(send_i, end_{i-1}),  end_i = start_i + size/bw,
      visibility_i = end_i + latency.
    The FIFO recurrence is a max-plus scan — computed with an associative
    scan over (shift, add) pairs, segmented by link_id.  ``ser_ns``
    bypasses the float32 serialization math with exact precomputed
    per-message durations (see kernels.hub_route).
    """
    if ser_ns is not None:
        ser = ser_ns.astype(jnp.int32)
    else:
        ser = (size_bytes.astype(jnp.float32) * 1e9
               / link_bw_Bps[link_id]).astype(jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             link_id[1:] != link_id[:-1]])

    # FIFO recurrence  end_i = max(send_i, end_{i-1}) + ser_i  as a
    # segmented max-plus associative scan.  Each message is the function
    # f_i(x) = max(x, send_i) + ser_i represented as (S=send_i, A=ser_i);
    # composition (f2 after f1) = (max(S1, S2 - A1), A1 + A2), and with
    # x0 = -inf the prefix composition gives end_i = S_i' + A_i'.
    # Segment starts (new link) reset the composition.
    def combine(e1, e2):
        s1, a1, seg1 = e1
        s2, a2, seg2 = e2
        s = jnp.where(seg2, s2, jnp.maximum(s1, s2 - a1))
        a = jnp.where(seg2, a2, a1 + a2)
        return s, a, seg1 | seg2

    s, a, _ = jax.lax.associative_scan(combine, (send_vtime, ser, first))
    return s + a + link_lat_ns[link_id]


def hub_visibility_ref(send_vtime, size_bytes, link_id, link_bw_Bps,
                       link_lat_ns, ser_ns=None):
    """Sequential oracle for hub_visibility (numpy)."""
    send_vtime = np.asarray(send_vtime)
    size_bytes = np.asarray(size_bytes)
    link_id = np.asarray(link_id)
    busy: dict = {}
    out = np.zeros_like(send_vtime)
    for i in range(len(send_vtime)):
        l = int(link_id[i])
        ser = (int(ser_ns[i]) if ser_ns is not None
               else int(size_bytes[i] * 1e9 / float(link_bw_Bps[l])))
        start = max(int(send_vtime[i]), busy.get(l, 0))
        end = start + ser
        busy[l] = end
        out[i] = end + int(link_lat_ns[l])
    return out
