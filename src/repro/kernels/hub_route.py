"""Batched hub message-routing Pallas TPU kernel (simulation-aware IPC
fast path, paper §3.4).

Computes visibility times for a batch of messages with per-link FIFO
queuing — the hub's common-path latency control as one vectorized pass:

  end_i = max(send_i, end_{i-1 on same link}) + size_i/bw
  visibility_i = end_i + latency

The FIFO recurrence is a segmented max-plus scan (elements (S, A) with
composition (max(S1, S2-A1), A1+A2)); within a VMEM tile it runs as a
log-depth doubling over one (1, block) lane row — shifts are
``pltpu.roll`` plus a lane mask — and the running prefix + link id carry
across tiles in (1, 1) VMEM scratch (grid ``arbitrary``).

Messages must be pre-sorted by (link_id, send_vtime) — the hub batches
per flush epoch, so the sort amortizes.  Oracle:
``repro.core.engine_jax.hub_visibility_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -(2**30)  # python int: jnp scalars would be captured as consts
_LOW = -(2**31)  # below every int32 value: identity of a lane max


def _last_lane(x, lane, block):
    """(1, 1) value of the row's last lane (a masked lane max: Mosaic
    has no dynamic_slice to extract it directly)."""
    return jnp.max(jnp.where(lane == block - 1, x, _LOW), axis=1,
                   keepdims=True)


def _kernel(send_ref, ser_ref, link_ref, lat_ref, out_ref,
            s_run, a_run, last_link, *, block):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        s_run[...] = jnp.full_like(s_run, NEG)
        a_run[...] = jnp.zeros_like(a_run)
        last_link[...] = jnp.full_like(last_link, -1)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    link = link_ref[...]
    prev_link = jnp.where(lane == 0, last_link[...],
                          pltpu.roll(link, 1, 1))
    # segment-start flags as int32: the doubling rolls them with S and A
    S, A = send_ref[...], ser_ref[...]
    G = (link != prev_link).astype(jnp.int32)

    # in-tile segmented max-plus scan via doubling; lanes below the
    # shift take the monoid identity (NEG, 0, no boundary), so tile-start
    # prefixes compose with a no-op rather than a fake boundary
    for st in range(int(math.log2(block))):
        d = 1 << st
        has = lane >= d
        S_sh = jnp.where(has, pltpu.roll(S, d, 1), NEG)
        A_sh = jnp.where(has, pltpu.roll(A, d, 1), 0)
        G_sh = jnp.where(has, pltpu.roll(G, d, 1), 0)
        first = G != 0
        S_new = jnp.where(first, S, jnp.maximum(S_sh, S - A_sh))
        A_new = jnp.where(first, A, A_sh + A)
        S, A, G = S_new, A_new, G | G_sh

    # fold the cross-tile carry into prefixes with no boundary yet
    first = G != 0
    S_fin = jnp.where(first, S, jnp.maximum(s_run[...], S - a_run[...]))
    A_fin = jnp.where(first, A, a_run[...] + A)
    out_ref[...] = S_fin + A_fin + lat_ref[...]

    s_run[...] = _last_lane(S_fin, lane, block)
    a_run[...] = _last_lane(A_fin, lane, block)
    last_link[...] = _last_lane(link, lane, block)


def hub_route(send_vtime, size_bytes, link_id, link_bw_Bps, link_lat_ns,
              *, ser_ns=None, block=2048, interpret=False):
    """Visibility times (ns int32) for sorted messages.

    send_vtime (M,) int32; size_bytes (M,) int32; link_id (M,) int32;
    link_bw_Bps/link_lat_ns (L,) per-link tables.  ``ser_ns`` (M,)
    bypasses the float32 size/bandwidth serialization math with exact
    precomputed per-message durations — the vectorized engine's
    tick-quantized tapes need bit-exact integer queuing (float32 only
    carries 24 mantissa bits, so ``size * 1e9`` already rounds)."""
    m = send_vtime.shape[0]
    if ser_ns is not None:
        ser = ser_ns.astype(jnp.int32)
    else:
        ser = (size_bytes.astype(jnp.float32) * 1e9
               / link_bw_Bps[link_id]).astype(jnp.int32)
    lat = link_lat_ns[link_id].astype(jnp.int32)
    # a lane row of at least 128: Mosaic rolls whole vregs
    block = min(block, max(128, 1 << int(math.ceil(math.log2(max(m, 1))))))
    assert block & (block - 1) == 0
    m_pad = pl.cdiv(m, block) * block
    if m_pad != m:
        pad = (0, m_pad - m)
        send_vtime = jnp.pad(send_vtime, pad)
        ser = jnp.pad(ser, pad)
        # padded tail gets a fresh fake link so it can't affect carries
        link_id = jnp.pad(link_id, pad, constant_values=2**30)
        lat = jnp.pad(lat, pad)

    row = pl.BlockSpec((1, block), lambda j: (0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid=(m_pad // block,),
        in_specs=[row, row, row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, m_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.int32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hub_route",
    )(*(x.reshape(1, m_pad) for x in (send_vtime, ser, link_id, lat)))
    return out[0, :m]
