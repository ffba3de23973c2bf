"""The vectorized facade engine: compile a Simulation to arrays, run
the jitted round loop (``repro.core.engine_jax``), decompile back to a
normal :class:`~repro.sim.report.SimReport`.

``Simulation.run(engine="vectorized")`` is the fifth engine, held to
the same cross-engine equivalence bar as single/barrier/async/dist via
a *two-tier* contract (tests/engine_harness.py):

* **exact tier** — every additive ns quantity of the scenario (compute
  durations post-straggler, the scheduler's send overhead, per-message
  serialization and latency, DegradeLink extras) is divisible by the
  compiled tick (auto tick = their gcd, so auto-ticked scenarios are
  always exact when they fit the range): results are **bit-identical**
  to the reference engines, including per-link stats.
* **tolerance tier** — an explicit ``tick_ns=`` quantizes those
  quantities: per-task vtimes carry a declared bound
  (``tick * n_quantities`` — each additive term appears at most once on
  any event's max-plus dependency path), while the schedule-independent
  invariants (completion sets, per-task states, message/byte totals,
  progress arrays) stay exact.

Admissible scenario surface (everything else raises
:class:`UnsupportedByEngine` at build time, never silently diverges):
modeled programs lowered via ``Workload.vec_ops`` (RackRing,
ChipRingTraining), any topology/placement, Straggler / FailTask /
FailHost / DegradeLink / Interference injections, bounded-skew scopes.
Not admissible: live programs (real callables can't be arrays), §3.3
cells (stateful per-dispatch charges), ``cpu_resource`` (CPU-slot
schedules are engine timing, not results), multi-producer endpoints
(receive matching becomes schedule-dependent — e.g. ModeledServe), and
scenarios the reference would preempt (>= ``preempt_after`` consecutive
zero-progress computes).

Why the restricted surface is *provably* schedule-independent: each
channel has a single producer executing its sends in program order, so
per-channel FIFO busy chains and message visibilities depend only on
the producer's vtime trajectory; each receive is matched to one message
at compile time and resolves to ``vtime = max(vtime, visibility)``;
scope gating and CPU slots delay dispatch but never change any of those
values.  Hence dispatch-all-eligible-per-round produces the reference
fixpoint exactly.
"""
from __future__ import annotations

import dataclasses
import inspect
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core import engine_jax as ej
from repro.core.scheduler import Scheduler
from repro.core.vtime import SEC
from repro.sim.report import HostReport, SimReport, _jsonable
from repro.sim.scenario import (BitFlip, ClockSkew, DegradeLink,
                                FailTask, Interference, JoinHost,
                                Scenario)
from repro.sim.workload import VecCompute, VecMark, VecRecv, VecSend

__all__ = ["UnsupportedByEngine", "compile_simulation",
           "run_vectorized_sim", "sweep_vectorized", "SweepResult"]

#: reference-engine constants, read off Scheduler so a recalibration
#: there cannot silently diverge this engine
_SCHED_DEFAULTS = {
    p.name: p.default
    for p in inspect.signature(Scheduler.__init__).parameters.values()}
SEND_OVERHEAD_NS = int(_SCHED_DEFAULTS["send_overhead_ns"])
PREEMPT_AFTER = int(_SCHED_DEFAULTS["preempt_after"])

_INF = ej.INF_TICKS
_I64_MAX = int(np.iinfo(np.int64).max)


class UnsupportedByEngine(ValueError):
    """The scenario uses a feature outside the vectorized engine's
    admissible surface (see module docstring).  Raised at build time so
    an unsupported run is an explicit error, not a silent divergence."""


def _ser_ns(size_bytes: int, link) -> int:
    # exactly Hub._serialize's expression
    return int(size_bytes * 8 / link.bandwidth_bps * SEC)


@dataclasses.dataclass
class _Msg:
    src_ep: str
    dst_ep: str
    size: int
    src_task: int
    src_host: int
    dst_host: int
    ch1: int
    ser1: int               # ns
    lat1: int               # ns
    two_stage: bool
    ch2: int
    ser2: int               # ns
    lat2: int               # ns


@dataclasses.dataclass
class CompiledSim:
    """Tick-level arrays (``tape``) + everything decompile needs."""
    tape: "ej.VecTape"
    n_channels: int
    tick_ns: int
    tier: str                       # "exact" | "tolerance"
    tol_ns: int                     # declared vtime bound (0 = exact)
    max_rounds: int
    n_tasks: int
    n_programs: int                 # leading tasks that are programs
    task_names: List[str]
    task_hosts: List[int]
    #: per task: (op_index, workload_index, array, index, value); fires
    #: iff final pc >= op_index
    markers: List[List[Tuple[int, int, str, int, int]]]
    msgs: List[_Msg]
    hub_base: str                   # multi-host hub name prefix
    n_hosts: int
    scenario_name: str
    #: additive ns quantities, int64 (for sweep: shared-tick computation)
    quantities: np.ndarray
    #: the lowered structure it was quantized from (see :func:`_lower`)
    structure: Dict[str, Any] = dataclasses.field(repr=False)


# ---------------------------------------------------------------------------
# lowering: facade -> ns-level arrays
# ---------------------------------------------------------------------------


def _detect_cells(sim, programs, inter_targets) -> bool:
    cell_of = {p.name: p.cell for _, p in programs if p.cell}
    load_cells = [inj.cell for inj, _ in inter_targets]
    if sim.cells_mode == "auto":
        prog_hosts: Dict[int, List[str]] = {}
        for _, p in programs:
            prog_hosts.setdefault(sim.placement[p.name],
                                  []).append(p.name)
        load_hosts = {h for _, h in inter_targets}
        for h, names in prog_hosts.items():
            if len(names) >= 2 or h in load_hosts:
                return True
        if load_cells:
            return True
    return bool(cell_of) or any(c is not None for c in load_cells)


def _check_surface(sim) -> None:
    """The admissibility checks a scenario's injections can fail."""
    if sim.cpu_resource:
        raise UnsupportedByEngine(
            "cpu_resource=True: CPU-slot contention is an engine "
            "schedule, not an array op")
    if getattr(sim.topology, "joins", None) or any(
            isinstance(inj, JoinHost) for inj in sim.scenario.injections):
        raise UnsupportedByEngine(
            "membership joins: late hosts need the conservative "
            "engines' membership-epoch re-solve; the vectorized "
            "compiler lowers a fixed host set")
    for inj in sim.scenario.injections:
        # explicit rejection, not silent omission: a campaign's sweep
        # fast path relies on this raise to fall back to the reference
        # engines for data-corruption / ingress-skew grids
        if isinstance(inj, BitFlip):
            raise UnsupportedByEngine(
                "BitFlip: payload values have no vectorized lowering "
                "(tapes carry sizes and timing, not data)")
        if isinstance(inj, ClockSkew):
            raise UnsupportedByEngine(
                "ClockSkew: ingress hooks are per-delivery hub state, "
                "not a tape-time transform")


def _shares_structure(sim, low: Dict[str, Any]) -> bool:
    """Whether ``sim`` lowers to the structure of ``low``: the same
    topology, workloads and placement (as a sweep's variants have) and
    an equal resolved Interference list.  Sets ``sim.placement`` as
    :func:`_lower` does."""
    st = low["structure"]
    workloads, spec = st["owner"]
    if not (sim.topology is st["topology"]
            and len(sim.workloads) == len(workloads)
            and all(a is b for a, b in zip(sim.workloads, workloads))
            and (sim.placement_spec, sim.capacity, sim.cpu_resource,
                 sim.cells_mode) == spec):
        return False
    sim.placement = dict(st["placement"])
    return sim._resolve_interference() == st["inter_targets"]


def _lower(sim, shared: Optional[Dict[str, Any]] = None
           ) -> Dict[str, Any]:
    """Validate the scenario against the admissible surface and lower
    it to ns-level arrays (tick-independent).  A lowering is the
    scenario's ``structure`` — tapes, messages, channels, scopes, and
    the unscaled compute ns per tape position — which its injections
    shape only through the resolved Interference list, plus the values
    its other injections set: Straggler-scaled compute ns, fail points
    and DegradeLink extras.  ``shared`` is the lowering of another
    variant of the same Simulation (a sweep's first); when ``sim`` has
    its structure (:func:`_shares_structure`) only the values are
    lowered, after the same checks on them."""
    if shared is not None and _shares_structure(sim, shared):
        st = shared["structure"]
        _check_surface(sim)
        scale, fails = sim._resolve_fault_plan(st["names"])
        comp_ns = _scaled_ns(st, scale)
        _check_progress(st, comp_ns)
        return _values(sim, st, comp_ns, fails)

    topo = sim.topology
    programs = sim._programs()
    fabrics = sim._fabrics()
    names = [p.name for _, p in programs]
    placement = sim._resolve_placement(names)
    sim.placement = placement
    inter_targets = sim._resolve_interference()

    _check_surface(sim)
    for _, p in programs:
        if p.kind != "modeled":
            raise UnsupportedByEngine(
                f"live program {p.name!r}: real callables have no "
                f"vectorized lowering")
    if _detect_cells(sim, programs, inter_targets):
        raise UnsupportedByEngine(
            "memory-hierarchy cells: per-dispatch cell charges are "
            "stateful scheduler semantics")

    # workload lowering
    ops_by_name: Dict[str, list] = {}
    wl_of_prog: Dict[str, int] = {}
    for wi, wl in enumerate(sim.workloads):
        wl_progs = [p.name for w, p in programs if w is wl]
        vec = wl.vec_ops()
        if vec is None:
            raise UnsupportedByEngine(
                f"workload {wl.name!r} has no vec_ops() lowering")
        missing = [n for n in wl_progs if n not in vec]
        if missing:
            raise ValueError(
                f"vec_ops() of {wl.name!r} missing programs {missing}")
        for n in wl_progs:
            ops_by_name[n] = list(vec[n])
            wl_of_prog[n] = wi

    # endpoints (mirrors the build() spawn loop's wiring checks)
    ep_owner: Dict[str, str] = {}
    ep_fabric: Dict[str, str] = {}
    fabric_idx = {f.name: i for i, f in enumerate(fabrics)}
    for _, p in programs:
        for es in p.endpoints:
            if es.name in ep_owner:
                raise ValueError(f"duplicate endpoint {es.name!r}")
            if es.fabric not in fabric_idx:
                raise KeyError(f"unknown fabric {es.fabric!r}")
            ep_owner[es.name] = p.name
            ep_fabric[es.name] = es.fabric

    scale, fails = sim._resolve_fault_plan(names)

    # task list: programs (report-visible) then interference loads; each
    # task's real ops (marks stripped) as positions of their kind
    markers: List[List[Tuple[int, int, str, int, int]]] = []
    task_names: List[str] = []
    task_hosts: List[int] = []
    n_ops: List[int] = []
    comp_rows: List[int] = []
    comp_cols: List[int] = []
    comp_ns: List[int] = []
    sends: List[Tuple[int, int, VecSend]] = []
    recvs: List[Tuple[int, int, VecRecv]] = []
    for t, (_, p) in enumerate(programs):
        j = 0
        marks: List[Tuple[int, int, str, int, int]] = []
        for op in ops_by_name[p.name]:
            if isinstance(op, VecMark):
                marks.append((j, wl_of_prog[p.name],
                              op.array, op.index, op.value))
                continue
            if isinstance(op, VecCompute):
                comp_rows.append(t)
                comp_cols.append(j)
                comp_ns.append(op.ns)
            elif isinstance(op, (VecSend, VecRecv)):
                if ep_owner.get(op.endpoint) != p.name:
                    raise ValueError(
                        f"program {p.name!r} uses endpoint "
                        f"{op.endpoint!r} it does not own")
                (sends if isinstance(op, VecSend) else recvs).append(
                    (t, j, op))
            else:
                raise UnsupportedByEngine(
                    f"program {p.name!r}: op {op!r} has no vectorized "
                    f"form")
            j += 1
        n_ops.append(j)
        markers.append(marks)
        task_names.append(p.name)
        task_hosts.append(placement[p.name])
    n_programs = len(programs)
    for i, (inj, host) in enumerate(inter_targets):
        bursts = range(inj.bursts)
        comp_rows.extend([len(n_ops)] * len(bursts))
        comp_cols.extend(bursts)
        comp_ns.extend([inj.burst_ns] * len(bursts))
        n_ops.append(len(bursts))
        markers.append([])
        task_names.append(f"load{i}")
        task_hosts.append(host)
    n_tasks = len(n_ops)
    rows = np.array(comp_rows, np.int64)
    st: Dict[str, Any] = dict(
        owner=(tuple(sim.workloads),
               (sim.placement_spec, sim.capacity, sim.cpu_resource,
                sim.cells_mode)),
        placement=placement, inter_targets=inter_targets, names=names,
        name_idx={n: i for i, n in enumerate(names)},
        task_names=task_names, task_hosts=task_hosts,
        n_programs=n_programs, markers=markers,
        n_ops=np.array(n_ops, np.int32), comp_rows=rows,
        comp_cols=np.array(comp_cols, np.int64),
        comp_ns=np.array(comp_ns, np.int64),
        comp_off=np.concatenate(
            ([0], np.cumsum(np.bincount(rows, minlength=n_tasks)))),
        topology=topo, fabrics=fabrics, fabric_idx=fabric_idx,
        hub_base=fabrics[0].name if fabrics else "hub",
        n_hosts=topo.n_hosts)
    scaled = _scaled_ns(st, scale)
    _check_progress(st, scaled)

    # messages + channels.  Pass 1: sends, in task/program order (=
    # per-channel FIFO order); pass 2: receive matching.
    channels: Dict[tuple, int] = {}

    def chan(key: tuple) -> int:
        return channels.setdefault(key, len(channels))

    msgs: List[_Msg] = []
    cols: List[tuple] = []      # per message: its numeric fields
    sends_to: Dict[str, List[int]] = {}
    dst_sources: Dict[str, set] = {}
    peer_producers: Dict[tuple, set] = {}
    for t, _, op in sends:
        if op.dst not in ep_owner:
            raise KeyError(f"unknown endpoint {op.dst!r}")
        fs, fd = ep_fabric[op.endpoint], ep_fabric[op.dst]
        if fs != fd:
            raise UnsupportedByEngine(
                f"cross-fabric send {op.endpoint!r}->{op.dst!r} "
                f"({fs!r} vs {fd!r})")
        flink = fabrics[fabric_idx[fs]].link
        sh = placement[ep_owner[op.endpoint]]
        dh = placement[ep_owner[op.dst]]
        if sh == dh:
            m = _Msg(op.endpoint, op.dst, op.size_bytes, t, sh, dh,
                     ch1=chan(("ep", op.endpoint, op.dst)),
                     ser1=_ser_ns(op.size_bytes, flink),
                     lat1=flink.latency_ns, two_stage=False,
                     ch2=0, ser2=0, lat2=0)
        else:
            plink = topo.host_link(sh, dh)
            key = ("peer", sh, dh)
            peer_producers.setdefault(key, set()).add(t)
            m = _Msg(op.endpoint, op.dst, op.size_bytes, t, sh, dh,
                     ch1=chan(key),
                     ser1=_ser_ns(op.size_bytes, plink),
                     lat1=plink.latency_ns, two_stage=True,
                     ch2=chan(("ep", op.endpoint, op.dst)),
                     ser2=_ser_ns(op.size_bytes, flink),
                     lat2=flink.latency_ns)
        sends_to.setdefault(op.dst, []).append(len(msgs))
        dst_sources.setdefault(op.dst, set()).add(op.endpoint)
        msgs.append(m)
        cols.append((m.ch1, m.ser1, m.lat1, m.two_stage, m.ch2, m.ser2,
                     m.lat2, sh, dh, fabric_idx[fs]))
    n_msgs = len(msgs)
    multi = sorted(ep for ep, srcs in dst_sources.items()
                   if len(srcs) > 1)
    if multi:
        raise UnsupportedByEngine(
            f"endpoints {multi} receive from multiple source "
            f"endpoints: receive matching would depend on the engine "
            f"schedule")
    multi_peer = sorted(k[1:] for k, ts in peer_producers.items()
                        if len(ts) > 1)
    if multi_peer:
        raise UnsupportedByEngine(
            f"host pairs {multi_peer} carry cross-host sends from "
            f"multiple producer tasks: peer-channel FIFO order would "
            f"depend on the engine schedule")
    recv_arg: List[int] = []
    recv_count: Dict[str, int] = {}
    for _, _, op in recvs:
        k = recv_count.get(op.endpoint, 0)
        recv_count[op.endpoint] = k + 1
        matched = sends_to.get(op.endpoint, [])
        # unmatched -> the never-sent sentinel row (blocks forever)
        recv_arg.append(matched[k] if k < len(matched) else n_msgs)

    # the tape arrays: op kinds, and each send's and receive's message
    p = max(1, max(n_ops, default=0))
    op_kind = np.zeros((n_tasks, p), np.int32)
    op_arg = np.zeros((n_tasks, p), np.int32)
    op_kind[rows, st["comp_cols"]] = ej.OP_COMPUTE
    for kind, pos, args in ((ej.OP_SEND, sends, np.arange(n_msgs)),
                            (ej.OP_RECV, recvs, recv_arg)):
        rc = np.array([(t, j) for t, j, _ in pos], np.int64).reshape(-1, 2)
        op_kind[rc[:, 0], rc[:, 1]] = kind
        op_arg[rc[:, 0], rc[:, 1]] = args
    (ch1, ser1, lat1, two, ch2, ser2, lat2, src_host, dst_host,
     msg_fabric) = np.array(cols, np.int64).reshape(-1, 10).T
    two = two.astype(bool)

    # scopes (loads never join)
    name_idx = st["name_idx"]
    scope_skews: List[int] = []
    names_by_wl: Dict[int, List[str]] = {}
    for wl, prog in programs:
        names_by_wl.setdefault(id(wl), []).append(prog.name)
    scope_members: List[List[int]] = []
    for wl in sim.workloads:
        wl_names = names_by_wl.get(id(wl), [])
        for ss in wl.scopes():
            scope_members.append([name_idx[m] for m in
                                  (ss.members or tuple(wl_names))])
            scope_skews.append(ss.skew_bound_ns)
    membership = np.zeros((n_tasks, len(scope_members)), bool)
    for j, members in enumerate(scope_members):
        membership[members, j] = True

    st.update(
        op_kind=op_kind, op_arg=op_arg, msgs=msgs,
        n_channels=len(channels), msg_ch1=ch1, msg_ser1=ser1,
        msg_lat1=lat1, msg_two_stage=two, msg_ch2=ch2, msg_ser2=ser2,
        msg_lat2=lat2, msg_src_host=src_host, msg_dst_host=dst_host,
        msg_fabric=msg_fabric,
        msg_qs=np.concatenate((np.full(n_msgs, SEND_OVERHEAD_NS, np.int64),
                               ser1, lat1, ser2[two], lat2[two])),
        membership=membership, scope_skews=scope_skews)
    return _values(sim, st, scaled, fails)


def _scaled_ns(st: Dict[str, Any], scale: Dict[str, float]) -> np.ndarray:
    """Compute ns per tape position, each program's scaled by its
    Straggler factor as the reference engines scale (``int(ns *
    factor)``: a float64 product, truncated), always from the unscaled
    ns."""
    ns = st["comp_ns"]
    if not scale:
        return ns
    ns = ns.copy()
    off = st["comp_off"]
    for name, factor in scale.items():
        i = st["name_idx"][name]
        a, b = off[i], off[i + 1]
        prod = st["comp_ns"][a:b] * factor
        if not (np.abs(prod) < 2.0 ** 63).all():
            raise ej.TickRangeError(
                f"Straggler {name!r} x{factor}: compute ns beyond int64")
        ns[a:b] = prod.astype(np.int64)
    return ns


def _check_progress(st: Dict[str, Any], comp_ns: np.ndarray) -> None:
    """Refuse a task that the reference would preempt: ``PREEMPT_AFTER``
    consecutive zero-progress computes.  The reference counter resets on
    *progress*, so interleaved sends/recvs don't break a zero run."""
    zero = comp_ns <= 0
    if not zero.any():
        return
    off = st["comp_off"]
    for t in np.unique(st["comp_rows"][zero]):
        run = 0
        for z in zero[off[t]:off[t + 1]]:
            run = run + 1 if z else 0
            if run >= PREEMPT_AFTER:
                raise UnsupportedByEngine(
                    f"task {st['task_names'][t]!r}: >= {PREEMPT_AFTER} "
                    f"consecutive zero-progress computes — the "
                    f"reference scheduler would preempt it FAULTY")


def _values(sim, st: Dict[str, Any], comp_ns: np.ndarray,
            fails: Dict[str, FailTask]) -> Dict[str, Any]:
    """A lowering: the structure ``st`` with the scenario's values."""
    # DegradeLink hooks -> per-message (from_vtime, extra), as a mask of
    # the messages each adds to (sender-side stage-1 only, exactly like
    # Hub.route's hook pass)
    degrades: List[Tuple[np.ndarray, int, int]] = []
    for inj in sim.scenario.injections:
        if not isinstance(inj, DegradeLink):
            continue
        if (inj.fabric is None) == (inj.hosts is None):
            raise ValueError("DegradeLink needs exactly one of "
                             "fabric= or hosts=")
        if inj.fabric is not None:
            fi = st["fabric_idx"].get(inj.fabric)
            if fi is None:
                raise ValueError(f"unknown fabric {inj.fabric!r}")
            extra = inj.extra_ns + int(
                (inj.latency_factor - 1.0) *
                st["fabrics"][fi].link.latency_ns)
            match = st["msg_fabric"] == fi
        else:
            a, b = inj.hosts
            pair_link = st["topology"].host_link(a, b)
            extra = inj.extra_ns + int(
                (inj.latency_factor - 1.0) * pair_link.latency_ns)
            src, dst = st["msg_src_host"], st["msg_dst_host"]
            match = ((src == a) & (dst == b)) | ((src == b) & (dst == a))
        if extra < 0:
            raise ValueError("DegradeLink may only add latency "
                             "(conservative lookahead)")
        degrades.append((match, inj.from_vtime, extra))

    # fail points: at_compute -> tape index of the k-th (0-based)
    # compute op; at_vtime -> checked at every op boundary
    fail_pc: Dict[int, int] = {}
    fail_vt: Dict[int, int] = {}
    off = st["comp_off"]
    for name, f in fails.items():
        i = st["name_idx"][name]
        if f.at_vtime is not None:
            fail_vt[i] = f.at_vtime
        if f.at_compute is not None and \
                0 <= f.at_compute < off[i + 1] - off[i]:
            fail_pc[i] = int(st["comp_cols"][off[i] + f.at_compute])
    return dict(structure=st, comp_ns=comp_ns, degrades=degrades,
                fail_pc=fail_pc, fail_vt=fail_vt,
                scenario_name=sim.scenario.name)


def _quantities(low: Dict[str, Any]) -> np.ndarray:
    """Every additive ns quantity of the lowered scenario (each appears
    at most once on any event time's max-plus dependency path)."""
    return np.concatenate(
        [low["comp_ns"], low["structure"]["msg_qs"]] +
        [np.full(int(match.sum()), extra, np.int64)
         for match, _, extra in low["degrades"]])


def _gcd(qs: np.ndarray) -> int:
    """The gcd of the positive quantities (0 when there are none)."""
    pos = qs[qs > 0]
    return int(np.gcd.reduce(pos)) if pos.size else 0


# ---------------------------------------------------------------------------
# quantization: ns -> ticks
# ---------------------------------------------------------------------------


def _quantize(low: Dict[str, Any], tick_ns: Optional[int],
              shared: Optional[CompiledSim] = None) -> CompiledSim:
    """Quantize a lowering to ticks.  ``shared`` is the quantization of
    another lowering with the same structure (a sweep's first): at the
    same tick its structural arrays are reused as they are, and only
    this lowering's values — compute ticks, fail points, DegradeLink
    extras — are made."""
    st = low["structure"]
    qs = _quantities(low)
    gcd = _gcd(qs)
    if tick_ns is None:
        tick = gcd or 1
    else:
        if tick_ns < 1:
            raise ValueError(f"tick_ns must be >= 1, got {tick_ns}")
        tick = int(tick_ns)
    # conservative horizon bound: any event time is a max-plus path sum
    # over distinct additive quantities <= their total sum
    pos = qs[qs > 0]
    total_ns = (sum(pos.tolist())
                if pos.size and int(pos.max()) > _I64_MAX // pos.size
                else int(pos.sum()))
    bound_ticks = total_ns // tick + qs.size + 1
    if bound_ticks >= _INF:
        raise ej.TickRangeError(
            f"scenario horizon bound {total_ns} ns = {bound_ticks} "
            f"ticks at tick_ns={tick} >= 2**30 — exceeds the int32 "
            f"tick range; pass a coarser tick_ns= (tolerance tier) or "
            f"shrink the scenario")
    exact = gcd % tick == 0         # tick divides every quantity
    tier = "exact" if exact else "tolerance"
    tol = 0 if exact else tick * qs.size

    def q_add(x):                   # additive quantity: round-half
        return (x + tick // 2) // tick

    def q_ceil(x: int) -> int:      # threshold: exact under >= cmp
        return min(-(-int(x) // tick), _INF)

    import jax.numpy as jnp
    n = len(st["n_ops"])
    op_arg = st["op_arg"].copy()
    op_arg[st["comp_rows"], st["comp_cols"]] = q_add(low["comp_ns"])
    fail_pc = np.full(n, _INF, np.int32)
    fail_vt = np.full(n, _INF, np.int32)
    for i, pc in low["fail_pc"].items():
        fail_pc[i] = pc
    for i, vt in low["fail_vt"].items():
        fail_vt[i] = q_ceil(vt)
    m = len(st["msgs"])
    slot = np.zeros(m, np.int64)
    for match, _, _ in low["degrades"]:
        slot += match
    d = int(slot.max(initial=0))
    extra = np.zeros((m, d), np.int32)
    extra_from = np.zeros((m, d), np.int32)
    slot[:] = 0
    for match, frm, ext in low["degrades"]:
        rows = np.flatnonzero(match)
        extra_from[rows, slot[rows]] = q_ceil(frm)
        extra[rows, slot[rows]] = q_add(int(ext))
        slot[rows] += 1
    values = dict(
        op_arg=jnp.asarray(op_arg), fail_pc=jnp.asarray(fail_pc),
        fail_vtime=jnp.asarray(fail_vt), msg_extra=jnp.asarray(extra),
        msg_extra_from=jnp.asarray(extra_from))
    if shared is not None and shared.structure is st \
            and shared.tick_ns == tick:
        tape = dataclasses.replace(shared.tape, **values)
    else:
        def ticks(x: np.ndarray):
            return jnp.asarray(q_add(x).astype(np.int32))
        skew = np.array([min(s // tick, _INF - 1)
                         for s in st["scope_skews"]], np.int32)
        tape = ej.VecTape(
            op_kind=jnp.asarray(st["op_kind"]),
            n_ops=jnp.asarray(st["n_ops"]),
            membership=jnp.asarray(st["membership"]),
            skew=jnp.asarray(skew),
            send_overhead=jnp.int32(q_add(SEND_OVERHEAD_NS)),
            msg_ch1=jnp.asarray(st["msg_ch1"].astype(np.int32)),
            msg_ser1=ticks(st["msg_ser1"]), msg_lat1=ticks(st["msg_lat1"]),
            msg_two_stage=jnp.asarray(st["msg_two_stage"]),
            msg_ch2=jnp.asarray(st["msg_ch2"].astype(np.int32)),
            msg_ser2=ticks(st["msg_ser2"]), msg_lat2=ticks(st["msg_lat2"]),
            **values)
    return CompiledSim(
        tape=tape, n_channels=st["n_channels"],
        tick_ns=tick, tier=tier, tol_ns=tol,
        max_rounds=int(st["n_ops"].sum()) + n + 3,
        n_tasks=n, n_programs=st["n_programs"],
        task_names=st["task_names"], task_hosts=st["task_hosts"],
        markers=st["markers"], msgs=st["msgs"], hub_base=st["hub_base"],
        n_hosts=st["n_hosts"], scenario_name=low["scenario_name"],
        quantities=qs, structure=st)


def compile_simulation(sim, tick_ns: Optional[int] = None) -> CompiledSim:
    """Lower + quantize ``sim`` for the vectorized engine.  Raises
    :class:`UnsupportedByEngine` for inadmissible scenarios and
    :class:`~repro.core.engine_jax.TickRangeError` when the horizon
    bound exceeds the int32 tick range at the chosen tick."""
    with obs.span("sim.lower"):
        low = _lower(sim)
    with obs.span("sim.quantize"):
        return _quantize(low, tick_ns)


# ---------------------------------------------------------------------------
# batched hub fan-out (kernels/hub_route with the jnp scan as oracle)
# ---------------------------------------------------------------------------


def _batched_visibility(comp: CompiledSim, sent: np.ndarray,
                        sent_vt: np.ndarray,
                        pallas: str) -> Optional[np.ndarray]:
    """Recompute every message's final visibility (ticks) with the
    batched segmented-scan fan-out pass — ``kernels.hub_route`` on the
    Pallas paths, the jnp associative scan otherwise.  Serialization
    durations come from the tick-quantized tape via the kernels'
    ``ser_ns=`` integer bypass (the float32 size*1e9/bw path only
    carries 24 mantissa bits), so the result is bit-equal to the round
    loop's incremental visibilities for every *sent* message (unsent
    messages form a per-channel suffix; their rows are garbage and
    masked by the caller).  Returns None when there are no messages."""
    import jax.numpy as jnp

    msgs = comp.msgs
    m = len(msgs)
    if m == 0:
        return None
    tape = comp.tape
    c = max(comp.n_channels, 1)
    ser1 = np.asarray(tape.msg_ser1)
    lat1_t = np.zeros(c, np.int32)
    lat2_t = np.zeros(c, np.int32)
    lat1_m = np.asarray(tape.msg_lat1)
    lat2_m = np.asarray(tape.msg_lat2)
    ch1 = np.asarray(tape.msg_ch1)
    ch2 = np.asarray(tape.msg_ch2)
    lat1_t[ch1] = lat1_m
    two = np.asarray(tape.msg_two_stage)
    lat2_t[ch2[two]] = lat2_m[two]
    extra = np.sum(
        np.where(sent_vt[:m, None] >= np.asarray(tape.msg_extra_from),
                 np.asarray(tape.msg_extra), 0),
        axis=1).astype(np.int64) if np.asarray(tape.msg_extra).size \
        else np.zeros(m, np.int64)
    bw = np.ones(c, np.float32)        # unused: ser_ns bypass
    use_pallas = pallas in ("on", "interpret")

    def fanout(send, ser, link_id, lat_t):
        with obs.span("sim.fanout"):
            if use_pallas:
                from repro.kernels.hub_route import hub_route
                out = hub_route(jnp.asarray(send, jnp.int32),
                                jnp.asarray(ser, jnp.int32),
                                jnp.asarray(link_id, jnp.int32),
                                jnp.asarray(bw),
                                jnp.asarray(lat_t, jnp.int32),
                                ser_ns=jnp.asarray(ser, jnp.int32),
                                interpret=pallas == "interpret")
            else:
                out = ej.hub_visibility(jnp.asarray(send, jnp.int32),
                                        jnp.asarray(ser, jnp.int32),
                                        jnp.asarray(link_id, jnp.int32),
                                        jnp.asarray(bw),
                                        jnp.asarray(lat_t, jnp.int32),
                                        ser_ns=jnp.asarray(ser, jnp.int32))
            return np.asarray(out, np.int64)

    # stage 1: all messages, per-channel program order (= array order
    # per channel; lexsort keeps it within each channel)
    o1 = np.lexsort((np.arange(m), ch1))
    end1 = np.empty(m, np.int64)
    end1[o1] = fanout(sent_vt[:m][o1], ser1[o1], ch1[o1], lat1_t) \
        - lat1_t[ch1[o1]]
    vis = end1 + lat1_m + extra
    # stage 2: cross-host messages only, keyed by their dest channel
    xi = np.flatnonzero(two)
    if xi.size:
        o2 = xi[np.argsort(ch2[xi], kind="stable")]
        vis2 = fanout(vis[o2], np.asarray(tape.msg_ser2)[o2], ch2[o2],
                      lat2_t)
        out = vis.copy()
        out[o2] = vis2
        vis = out
    return vis


# ---------------------------------------------------------------------------
# run + decompile
# ---------------------------------------------------------------------------


def _resolve_pallas(pallas: str) -> Tuple[bool, bool]:
    import jax
    if pallas not in ("auto", "on", "off", "interpret"):
        raise ValueError(f"pallas must be auto/on/off/interpret, "
                         f"got {pallas!r}")
    if pallas == "auto":
        pallas = "on" if jax.default_backend() == "tpu" else "off"
    return pallas != "off", pallas == "interpret"


def _decompile(sim, comp: CompiledSim, st, *, pallas: str,
               verify: bool) -> SimReport:
    """The report of one run's final state; its ``wall_s`` is left 0 for
    the caller, which times the whole call."""
    tick = comp.tick_ns
    vtime = np.asarray(st.vtime, np.int64)
    pc = np.asarray(st.pc)
    done = np.asarray(st.done)
    sent = np.asarray(st.sent)[:len(comp.msgs)]
    sent_vt = np.asarray(st.sent_vt, np.int64)
    vis_loop = np.asarray(st.vis, np.int64)[:len(comp.msgs)]
    rounds = int(st.rounds)

    bvis = _batched_visibility(comp, sent, sent_vt, pallas)
    if bvis is not None:
        vis = np.where(sent, bvis, vis_loop)
        if verify and sent.any() and \
                not np.array_equal(vis[sent], vis_loop[sent]):
            raise RuntimeError(
                "vectorized engine: batched hub fan-out disagrees "
                "with the round loop's visibilities")
    else:
        vis = vis_loop

    status, detail = "ok", ""
    if not done.all():
        blocked = [comp.task_names[i] for i in np.flatnonzero(~done)]
        status = "deadlock"
        detail = (f"vectorized fixpoint: no task eligible; blocked: "
                  f"{blocked}")

    tasks = {}
    for i in range(comp.n_programs):
        tasks[comp.task_names[i]] = {
            "vtime": int(vtime[i]) * tick,
            "state": "done" if done[i] else "blocked",
            "host": comp.task_hosts[i]}

    progress: Dict[str, Any] = {}
    arrays = [{k: np.zeros_like(v) for k, v in wl.progress().items()}
              for wl in sim.workloads]
    for i in range(comp.n_programs):
        for op_idx, wi, arr, index, value in comp.markers[i]:
            if pc[i] >= op_idx:
                arrays[wi][arr][index] = value
    for wl, arrs in zip(sim.workloads, arrays):
        progress[wl.name] = _jsonable(arrs)

    msgs_total = int(sent.sum())
    bytes_total = sum(m.size for m, s in zip(comp.msgs, sent) if s)
    links: Dict[str, Dict[str, Any]] = {}
    cross = 0
    for i, m in enumerate(comp.msgs):
        if not sent[i] or not m.two_stage:
            continue
        cross += 1
        key = (f"{comp.hub_base}{m.src_host}->"
               f"{comp.hub_base}{m.dst_host}")
        st_ = links.setdefault(key, {"messages": 0, "bytes": 0,
                                     "min_slack_ns": None,
                                     "max_visibility_ns": 0})
        st_["messages"] += 1
        st_["bytes"] += m.size
        slack = int(vis[i]) * tick - int(sent_vt[i]) * tick - m.lat1
        st_["min_slack_ns"] = (slack if st_["min_slack_ns"] is None
                               else min(st_["min_slack_ns"], slack))
        st_["max_visibility_ns"] = max(st_["max_visibility_ns"],
                                       int(vis[i]) * tick)

    host_disp = [0] * comp.n_hosts
    for i in range(comp.n_tasks):
        host_disp[comp.task_hosts[i]] += int(pc[i])
    hosts = [HostReport(host=h, dispatches=host_disp[h], rounds=rounds,
                        skew_stalls=0, max_skew_seen=0,
                        gate_deferrals=0, window_runs=0, preemptions=0,
                        live_calls=0)
             for h in range(comp.n_hosts)]

    horizon = int(vtime.max(initial=0)) * tick
    return SimReport(
        status=status, mode="vectorized", n_hosts=comp.n_hosts,
        vtime_ns=horizon, wall_s=0.0, messages=msgs_total,
        bytes=bytes_total, sync_rounds=rounds, proxy_syncs=0,
        cross_host_msgs=cross, max_proxy_staleness_ns=0,
        max_window_ns=0, hosts=hosts, links=links, tasks=tasks,
        progress=progress, scenario=comp.scenario_name, detail=detail,
        cells={}, tick_ns=tick, tier=comp.tier)


def run_vectorized_sim(sim, *, tick_ns: Optional[int] = None,
                       pallas: str = "auto",
                       max_rounds: Optional[int] = None,
                       verify: bool = False) -> SimReport:
    """Compile ``sim``, run the jitted round loop, decompile the
    resulting arrays to a :class:`SimReport` (``mode="vectorized"``).
    The report's ``wall_s`` is the whole call: lowering, the loop and
    decompiling, and any compilation they trigger."""
    import jax
    use_pallas, interpret = _resolve_pallas(pallas)
    with obs.span("sim.run"):
        t0 = time.perf_counter()
        comp = compile_simulation(sim, tick_ns)
        cap = comp.max_rounds if max_rounds is None else max_rounds
        with obs.span("sim.stage"):
            st0 = ej.init_vec_sim_state(comp.tape, comp.n_channels)
        with obs.span("sim.loop"):
            st = ej.run_vec_tape(comp.tape, st0, cap, pallas=use_pallas,
                                 interpret=interpret)
            jax.block_until_ready(st.vtime)
        if bool(st.progressed) and not bool(np.asarray(st.done).all()):
            raise RuntimeError(
                f"vectorized engine: max_rounds={cap} exhausted before "
                f"the fixpoint")
        with obs.span("sim.decompile"):
            report = _decompile(sim, comp, st,
                                pallas=("interpret" if interpret
                                        else "on" if use_pallas else "off"),
                                verify=verify)
        report.wall_s = time.perf_counter() - t0
    return report


# ---------------------------------------------------------------------------
# batched configuration sweep (jax.vmap over scenario variants)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SweepResult:
    """One compiled dispatch over V scenario variants.  ``wall_s`` is the
    whole :func:`sweep_vectorized` call (building, lowering and
    quantizing the variants, the batched loop, decompiling every
    report), ``configs_per_s`` is V over it, and each report's
    ``wall_s`` is its V-th share."""
    reports: List[SimReport]
    wall_s: float
    configs_per_s: float
    tick_ns: int
    tier: str


def _variant(sim, scenario: Scenario):
    """``sim`` with another scenario: its topology, workloads and
    placement, the objects themselves."""
    from repro.sim.simulation import Simulation
    return Simulation(sim.topology, sim.workloads, scenario,
                      placement=sim.placement_spec, mode=sim.mode,
                      capacity=sim.capacity, cpu_resource=sim.cpu_resource,
                      cells=sim.cells_mode)


def sweep_vectorized(sim, axis: List[Scenario], *,
                     tick_ns: Optional[int] = None,
                     max_rounds: Optional[int] = None) -> SweepResult:
    """Run one vectorized simulation per :class:`Scenario` in ``axis``
    as a single ``jax.vmap`` batch (shared compiled round loop, stacked
    tapes).  Variants must share scenario *structure* (same tapes,
    messages, channels — injections may change durations, fail points,
    degrade extras); a shared tick (gcd across variants) keeps every
    admissible variant on the exact tier.  The first variant is lowered
    and quantized in full; a later one whose resolved Interference list
    equals the first's lowers only its injection values onto that
    structure (its ``sim.lower`` span says ``path="shared"``, else
    ``"full"``).  Each returned report is bit-identical to running its
    variant alone (asserted in tests)."""
    import jax
    import jax.numpy as jnp

    if not axis:
        raise ValueError("sweep needs at least one Scenario")
    with obs.span("sim.sweep"):
        t0 = time.perf_counter()
        with obs.span("sim.variants"):
            variants = [_variant(sim, sc) for sc in axis]
        # the first variant's lowering is the structure the others share
        # where they can (_shares_structure): they lower their values only
        lows = []
        for v in variants:
            shared = lows[0] if lows and _shares_structure(v, lows[0]) \
                else None
            with obs.span("sim.lower",
                          path="full" if shared is None else "shared"):
                lows.append(_lower(v, shared=shared))
        if tick_ns is None:
            with obs.span("sim.quantize"):
                tick_ns = math.gcd(*(_gcd(_quantities(low))
                                     for low in lows)) or 1
        comps = []
        for low in lows:
            with obs.span("sim.quantize"):
                comps.append(_quantize(low, tick_ns,
                                       shared=comps[0] if comps else None))
        base = comps[0]
        with obs.span("sim.stage"):
            shapes = [jax.tree_util.tree_map(lambda x: jnp.shape(x), c.tape)
                      for c in comps]
            if any(sh != shapes[0] for sh in shapes[1:]):
                raise UnsupportedByEngine(
                    "sweep variants must share scenario structure (same "
                    "tapes/messages/channels); only injection values may "
                    "vary")
            tapes = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                           *[c.tape for c in comps])
            states = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[ej.init_vec_sim_state(c.tape, base.n_channels)
                  for c in comps])
        cap = (max(c.max_rounds for c in comps)
               if max_rounds is None else max_rounds)
        with obs.span("sim.loop"):
            out = ej.run_vec_tape_batch(tapes, states, cap)
            jax.block_until_ready(out.vtime)
        reports = []
        for v, comp in enumerate(comps):
            with obs.span("sim.unstack"):
                st_v = jax.tree_util.tree_map(lambda x: x[v], out)
                if bool(st_v.progressed) and \
                        not bool(np.asarray(st_v.done).all()):
                    raise RuntimeError(
                        f"vectorized sweep variant {v}: max_rounds={cap} "
                        f"exhausted before the fixpoint")
            with obs.span("sim.decompile"):
                reports.append(_decompile(variants[v], comp, st_v,
                                          pallas="off", verify=False))
        wall = time.perf_counter() - t0
    for report in reports:
        report.wall_s = wall / len(reports)
    return SweepResult(reports=reports, wall_s=wall,
                       configs_per_s=len(reports) / wall if wall > 0
                       else float("inf"),
                       tick_ns=tick_ns, tier=base.tier)
