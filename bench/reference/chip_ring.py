"""Plain reference for the data-parallel ring job on a simulated fleet.

Written from the simulator's documented semantics, with no code of the
program under test.  One virtual task per chip; every step a chip

1. computes for ``compute_ns`` (times its straggler factor, truncated to
   whole ns);
2. sends ``ici_bytes`` to its right-hand neighbour in its pod's ring and
   then receives from its left-hand one;
3. if it leads its pod (local index 0, more than one pod), sends
   ``dcn_bytes`` to the next pod's leader and receives from the previous.

A send advances the sender by the host's ``send_overhead_ns``.  Each
(source, destination) pair is one FIFO channel: a message starts when it
is sent or when the channel's previous message has been serialized,
whichever is later, is serialized for ``floor(bytes * 8 / bits_per_s *
1e9)`` ns and becomes visible ``latency_ns`` later.  A receive moves the
receiver's clock forward to the visibility of the message it matches
(the k-th message sent to an endpoint matches its k-th receive).
Bounded-skew gating only delays dispatch; it changes no time above.

Every send of a step depends only on the sender's own clock, so a step
is evaluated for all chips at once with numpy, in int64.

The simulated events of a run are its messages and its task operations:
per step and chip a compute, a send and a receive, and per step and pod
leader one more send and receive (``events``).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

SEC = 1_000_000_000


def ser_ns(size_bytes: int, bw_Bps: float) -> int:
    """Serialization time as the simulated hub defines it (the same
    floating-point expression, so the truncation agrees to the ns)."""
    return int(size_bytes * 8 / (bw_Bps * 8) * SEC)


def _leaders(cfg: Dict) -> int:
    return cfg["n_pods"] if cfg["n_pods"] > 1 else 0


def messages(cfg: Dict) -> int:
    """Messages of one run: one per chip and step over ICI, one per
    leader and step over DCN."""
    n = cfg["n_pods"] * cfg["chips_per_pod"]
    return cfg["n_steps"] * (n + _leaders(cfg))


def events(cfg: Dict) -> int:
    """Simulated events of one run: its messages and its task operations
    (compute, send and receive per chip and step, and a send and a
    receive more per leader and step).  Stragglers change no count."""
    n = cfg["n_pods"] * cfg["chips_per_pod"]
    ops = cfg["n_steps"] * (3 * n + 2 * _leaders(cfg))
    return messages(cfg) + ops


def simulate(cfg: Dict, stragglers: Dict[str, float]) -> Dict:
    """The report fields of one run: ``{status, n_hosts, vtime_ns,
    messages, bytes, tasks, progress, cells, live, links}``.
    ``stragglers`` maps a chip name to its compute slowdown."""
    pods, cpp, steps = cfg["n_pods"], cfg["chips_per_pod"], cfg["n_steps"]
    cost = cfg["step_cost"]
    over = cfg["send_overhead_ns"]
    n = pods * cpp
    comp = np.full(n, cost["compute_ns"], np.int64)
    for name, factor in stragglers.items():
        c = int(name.removeprefix("chip"))
        comp[c] = int(comp[c] * factor)
    ici_ser = ser_ns(cost["ici_bytes"], cfg["ici_bw_Bps"])
    dcn_ser = ser_ns(cost["dcn_bytes"], cfg["dcn_bw_Bps"])
    chip = np.arange(n)
    local = chip % cpp
    left = chip - local + (local - 1) % cpp     # who sends to chip c
    leaders = chip[local == 0] if pods > 1 else chip[:0]
    prev_pod = (np.arange(pods) - 1) % pods

    v = np.zeros(n, np.int64)
    ici_busy = np.zeros(n, np.int64)     # channel chip c -> its right
    dcn_busy = np.zeros(pods, np.int64)  # channel pod p -> pod p + 1
    for _ in range(steps):
        v += comp
        sv = v + over
        end = np.maximum(sv, ici_busy) + ici_ser
        ici_busy = end
        vis = end + cfg["ici_lat_ns"]
        v = np.maximum(sv, vis[left])
        if leaders.size:
            sv = v[leaders] + over
            end = np.maximum(sv, dcn_busy) + dcn_ser
            dcn_busy = end
            vis = end + cfg["dcn_lat_ns"]
            v[leaders] = np.maximum(sv, vis[prev_pod])
    return {
        "status": "ok", "n_hosts": 1, "vtime_ns": int(v.max()),
        "messages": messages(cfg),
        "bytes": int(steps * (n * cost["ici_bytes"]
                              + leaders.size * cost["dcn_bytes"])),
        "tasks": {f"chip{c}": {"vtime": int(v[c]), "state": "done",
                               "host": 0} for c in range(n)},
        "progress": {"train": {"done_steps": [steps] * n}},
        "cells": {}, "live": {}, "links": {},
    }
