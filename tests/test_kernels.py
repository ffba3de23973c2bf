"""Per-kernel allclose vs. pure-jnp/numpy oracles, interpret mode on CPU.

Every kernel sweeps shapes (incl. non-divisible / padded cases) and
dtypes per the deliverable-(c) requirement."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine_jax import hub_visibility_ref
from repro.kernels import ref as kref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention_flat
from repro.kernels.hub_route import hub_route
from repro.kernels.minskew import minskew
from repro.kernels.mlstm_kernel import mlstm_chunkwise
from repro.kernels.rglru_scan import rglru_scan

RNG = np.random.default_rng(42)


def rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------- flash attn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "bh,hkv,sq,sk,hd,causal,window,bq,bk",
    [
        (4, 4, 128, 128, 64, True, 0, 64, 64),
        (4, 2, 128, 128, 64, True, 0, 64, 64),      # GQA 2:1
        (8, 2, 96, 96, 32, True, 0, 64, 64),        # padded seq
        (2, 1, 256, 256, 64, True, 64, 64, 64),     # sliding window
        (2, 2, 64, 192, 32, False, 0, 64, 64),      # cross attention
        (6, 3, 128, 128, 128, True, 0, 128, 128),   # MXU-aligned hd
    ])
def test_flash_attention_vs_ref(bh, hkv, sq, sk, hd, causal, window,
                                bq, bk, dtype):
    q = rand((bh, sq, hd), dtype)
    k = rand((hkv, sk, hd), dtype)
    v = rand((hkv, sk, hd), dtype)
    out = flash_attention_flat(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk, interpret=True)
    ref = kref.attention_flat_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **TOL[dtype])


# ---------------------------------------------------------------- decode attn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,hkv,s,hd,bs",
    [
        (2, 4, 4, 256, 64, 128),
        (2, 8, 2, 256, 64, 128),        # GQA 4:1
        (3, 4, 1, 300, 32, 128),        # MQA + padded seq
        (1, 16, 8, 512, 128, 256),
    ])
def test_decode_attention_vs_ref(b, h, hkv, s, hd, bs, dtype):
    q = rand((b, h, hd), dtype)
    k = rand((b, s, hkv, hd), dtype)
    v = rand((b, s, hkv, hd), dtype)
    lengths = jnp.asarray(RNG.integers(1, s + 1, size=b), jnp.int32)
    out = decode_attention(q, k, v, lengths, block_s=bs, interpret=True)
    ref = kref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **TOL[dtype])


def _decode_attention_repeated(q, k_cache, v_cache, cache_len):
    """The GQA-repeat formulation ``models.attention.decode_attention``
    had before it contracted per KV group: the oracle its output must
    equal for G = 1 and match within rounding otherwise."""
    from repro.models.attention import NEG_INF, _repeat_kv
    b, _, h, hd = q.shape
    sk = k_cache.shape[1]
    k = _repeat_kv(k_cache, h // k_cache.shape[2])
    v = _repeat_kv(v_cache, h // k_cache.shape[2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s * (1.0 / jnp.sqrt(jnp.float32(hd)))
    valid = jnp.arange(sk)[None, :] < jnp.reshape(cache_len, (-1, 1))
    s = jnp.where(jnp.broadcast_to(valid, (b, sk))[:, None, None, :], s,
                  NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("per_row_len", [False, True])
@pytest.mark.parametrize("group", [1, 2, 4, 7, 16])
def test_model_decode_attention_grouped(group, per_row_len, dtype):
    """``models.attention.decode_attention`` against the float32
    reference and the repeat formulation, on caches padded with large
    garbage past the valid length."""
    from repro.models.attention import decode_attention as model_decode
    b, hkv, s, hd = 3, 2, 40, 32
    h = hkv * group
    q = rand((b, 1, h, hd), dtype)
    k = np.asarray(RNG.standard_normal((b, s, hkv, hd)), np.float32)
    v = np.asarray(RNG.standard_normal((b, s, hkv, hd)), np.float32)
    lengths = (np.asarray([5, s - 3, 17], np.int32) if per_row_len
               else np.full((b,), 23, np.int32))
    for row, n in enumerate(lengths):
        k[row, n:] = 50.0 * RNG.standard_normal((s - n, hkv, hd))
        v[row, n:] = 1e3
    k, v = jnp.asarray(k, dtype), jnp.asarray(v, dtype)
    cache_len = jnp.asarray(lengths) if per_row_len else int(lengths[0])

    out = model_decode(q, k, v, cache_len)
    assert out.shape == (b, 1, h, hd) and out.dtype == dtype
    ref = kref.decode_attention_ref(q[:, 0], k, v, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out[:, 0], np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])
    old = _decode_attention_repeated(q, k, v, cache_len)
    if group == 1:
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(old, np.float32))
    else:
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(old, np.float32),
                                   **TOL[dtype])


# ---------------------------------------------------------------- rglru


@pytest.mark.parametrize(
    "b,s,w,bt,with_h0",
    [
        (2, 128, 64, 64, False),
        (2, 128, 64, 64, True),
        (1, 300, 32, 128, True),        # padded seq
        (3, 64, 128, 64, False),
        (2, 16, 8, 16, True),           # tiny
    ])
def test_rglru_scan_vs_ref(b, s, w, bt, with_h0):
    log_a = -jnp.abs(rand((b, s, w)) * 0.3)     # decays in (0, 1]
    bv = rand((b, s, w))
    h0 = rand((b, w)) if with_h0 else None
    out = rglru_scan(log_a, bv, h0, block_t=bt, interpret=True)
    ref = kref.rglru_ref(log_a, bv, h0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- mlstm


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "bh,s,hd,chunk",
    [
        (2, 128, 32, 64),
        (4, 256, 64, 128),
        (1, 64, 128, 64),
        (2, 128, 32, 128),              # single chunk
    ])
def test_mlstm_chunkwise_vs_sequential(bh, s, hd, chunk, dtype):
    q = rand((bh, s, hd), dtype, 0.3)
    k = rand((bh, s, hd), dtype, 0.3)
    v = rand((bh, s, hd), dtype, 0.3)
    ig = rand((bh, s), jnp.float32)
    fg = rand((bh, s), jnp.float32) + 2.0
    out = mlstm_chunkwise(q, k, v, ig, fg, chunk=chunk, interpret=True)
    # oracle: sequential step form over (B=bh, H=1) heads
    c0 = jnp.zeros((bh, 1, hd, hd), jnp.float32)
    n0 = jnp.zeros((bh, 1, hd), jnp.float32)
    ref, _ = kref.mlstm_seq_ref(q[:, :, None, :], k[:, :, None, :],
                                v[:, :, None, :], ig[:, :, None],
                                fg[:, :, None], c0, n0)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref[:, :, 0, :], np.float32), **tol)


def test_mlstm_matches_model_chunkwise():
    """Kernel == the model's jnp chunkwise form (exact same algorithm)."""
    from repro.models.xlstm import mlstm_chunkwise as model_chunkwise

    bh, s, hd = 3, 256, 32
    q, k, v = (rand((bh, s, hd), jnp.float32, 0.3) for _ in range(3))
    ig = rand((bh, s), jnp.float32)
    fg = rand((bh, s), jnp.float32) + 2.0
    out = mlstm_chunkwise(q, k, v, ig, fg, chunk=128, interpret=True)
    c0 = jnp.zeros((bh, 1, hd, hd), jnp.float32)
    n0 = jnp.zeros((bh, 1, hd), jnp.float32)
    ref, _ = model_chunkwise(q[:, :, None, :], k[:, :, None, :],
                             v[:, :, None, :], ig[:, :, None],
                             fg[:, :, None], c0, n0, chunk=128)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref[:, :, 0, :]),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- minskew


@pytest.mark.parametrize(
    "n,s,bn,bs",
    [
        (64, 16, 32, 8),
        (200, 40, 64, 16),              # padded both dims
        (512, 128, 512, 128),
        (1000, 3, 256, 8),
        (1100, 300, 512, 128),          # native blocks, 3x3 grid, padded
    ])
def test_minskew_vs_ref(n, s, bn, bs):
    vtime = jnp.asarray(RNG.integers(0, 10_000, n), jnp.int32)
    runnable = jnp.asarray(RNG.random(n) < 0.7, jnp.int8)
    membership = jnp.asarray(RNG.random((n, s)) < 0.3, jnp.int8)
    skew = jnp.asarray(RNG.integers(1, 500, s), jnp.int32)
    minima, elig = minskew(vtime, runnable, membership, skew,
                           block_n=bn, block_s=bs, interpret=True)
    ref_min, ref_elig = kref.minskew_ref(vtime, runnable != 0,
                                         membership != 0, skew)
    np.testing.assert_array_equal(np.asarray(minima), ref_min)
    np.testing.assert_array_equal(np.asarray(elig) != 0, ref_elig)


def test_minskew_matches_engine_jax():
    from repro.core.engine_jax import eligibility, scope_minima

    n, s = 300, 25
    vtime = jnp.asarray(RNG.integers(0, 10_000, n), jnp.int32)
    runnable = jnp.asarray(RNG.random(n) < 0.6)
    membership = jnp.asarray(RNG.random((n, s)) < 0.25)
    skew = jnp.asarray(RNG.integers(1, 500, s), jnp.int32)
    minima_k, elig_k = minskew(vtime, runnable.astype(jnp.int8),
                               membership.astype(jnp.int8), skew,
                               interpret=True)
    minima_e = scope_minima(vtime, runnable, membership)
    elig_e = eligibility(vtime, runnable, membership, skew, minima_e)
    np.testing.assert_array_equal(np.asarray(minima_k),
                                  np.asarray(minima_e))
    np.testing.assert_array_equal(np.asarray(elig_k) != 0,
                                  np.asarray(elig_e))


# ---------------------------------------------------------------- hub_route


@pytest.mark.parametrize(
    "m,n_links,block",
    [
        (64, 4, 64),
        (500, 7, 128),                  # padded
        (2048, 1, 512),                 # one hot link
        (33, 33, 64),                   # one msg per link
    ])
def test_hub_route_vs_ref(m, n_links, block):
    link_id = np.sort(RNG.integers(0, n_links, m)).astype(np.int32)
    send = np.zeros(m, np.int64)
    # per-link sorted send times
    for l in range(n_links):
        idx = np.where(link_id == l)[0]
        send[idx] = np.sort(RNG.integers(0, 100_000, len(idx)))
    size = RNG.integers(64, 65_536, m).astype(np.int32)
    bw = RNG.uniform(1e9, 100e9, n_links)
    lat = RNG.integers(100, 10_000, n_links).astype(np.int32)
    out = hub_route(jnp.asarray(send, jnp.int32), jnp.asarray(size),
                    jnp.asarray(link_id), jnp.asarray(bw, jnp.float32),
                    jnp.asarray(lat), block=block, interpret=True)
    ref = hub_visibility_ref(send, size, link_id, bw, lat)
    # serialization rounding: float32 vs float64 division -> +-1ns slop
    np.testing.assert_allclose(np.asarray(out, np.int64), ref, atol=16)


# ------------------------------------------------- minskew edge cases (sim)


INF = 2**30


def _minskew_case(vtime, runnable, membership, skew, **kw):
    vtime = jnp.asarray(vtime, jnp.int32)
    runnable = np.asarray(runnable, bool)
    membership = np.asarray(membership, bool)
    skew = jnp.asarray(skew, jnp.int32)
    minima, elig = minskew(vtime, jnp.asarray(runnable, jnp.int8),
                           jnp.asarray(membership, jnp.int8), skew,
                           interpret=True, **kw)
    ref_min, ref_elig = kref.minskew_ref(np.asarray(vtime), runnable,
                                         membership, np.asarray(skew))
    np.testing.assert_array_equal(np.asarray(minima), ref_min)
    np.testing.assert_array_equal(np.asarray(elig) != 0, ref_elig)
    return np.asarray(minima), np.asarray(elig) != 0


def test_minskew_all_masked():
    """No runnable member anywhere: minima must be INF and nothing may
    dispatch (a fixpoint round of the vectorized engine)."""
    n, s = 40, 6
    minima, elig = _minskew_case(
        RNG.integers(0, 10_000, n), np.zeros(n, bool),
        RNG.random((n, s)) < 0.4, RNG.integers(1, 500, s))
    assert (minima == INF).all()
    assert not elig.any()


def test_minskew_empty_scope():
    """A scope with zero members is INF-min and must not gate anyone
    (the `minima == INF` escape in the eligibility rule)."""
    n, s = 24, 4
    membership = RNG.random((n, s)) < 0.5
    membership[:, 2] = False                      # nobody in scope 2
    minima, elig = _minskew_case(
        RNG.integers(0, 10_000, n), np.ones(n, bool), membership,
        np.zeros(s, np.int32))
    assert minima[2] == INF
    # zero skew + all runnable: exactly the global-min members of each
    # populated scope dispatch, so someone must be eligible
    assert elig.any()


def test_minskew_sentinel_vtimes():
    """Blocked tasks park at vtime INF in the vectorized engine; INF
    lanes must neither win minima nor become eligible."""
    n, s = 16, 3
    vtime = RNG.integers(0, 10_000, n)
    vtime[::2] = INF
    runnable = np.ones(n, bool)
    runnable[::2] = False
    minima, elig = _minskew_case(vtime, runnable,
                                 np.ones((n, s), bool),
                                 RNG.integers(1, 100, s))
    assert (minima < INF).all()
    assert not elig[::2].any()


def test_minskew_int32_boundary():
    """vtimes near the top of the tick range: minima + skew crosses
    2**30 but must not wrap int32."""
    n, s = 12, 2
    vtime = (INF - 1 - RNG.integers(0, 2_000, n)).astype(np.int64)
    minima, elig = _minskew_case(vtime, np.ones(n, bool),
                                 np.ones((n, s), bool),
                                 np.full(s, 5_000, np.int32))
    assert (minima >= INF - 2_001).all()
    assert elig.all()                   # all within skew of the min


def test_minskew_tiny_shapes():
    """N and S far below one block (padding-dominated grid)."""
    minima, elig = _minskew_case([7], [True], [[True]], [0])
    assert minima[0] == 7 and elig[0]
    _minskew_case(RNG.integers(0, 100, 3), [True, False, True],
                  RNG.random((3, 2)) < 0.5, [10, 20])


# ------------------------------------------------ hub_route ser_ns bypass


@pytest.mark.parametrize("m,block", [(1, 64), (7, 64), (129, 64),
                                     (500, 128),
                                     (5000, 1024)])   # 5 tiles, carries
def test_hub_route_ser_ns_bitexact(m, block):
    """With integer ``ser_ns`` the kernel must match the sequential
    oracle *bit-exactly* — no float32 serialization slop.  This is the
    contract the vectorized sim engine's exact tier rides on (its tapes
    precompute tick-exact durations; f32 only carries 24 mantissa bits,
    so e.g. 163e9/1e9 would truncate to 162)."""
    n_links = 5
    link_id = np.sort(RNG.integers(0, n_links, m)).astype(np.int32)
    send = np.zeros(m, np.int64)
    for l in range(n_links):
        idx = np.where(link_id == l)[0]
        send[idx] = np.sort(RNG.integers(0, 50_000, len(idx)))
    ser = RNG.integers(0, 10_000, m).astype(np.int32)
    ser[RNG.random(m) < 0.2] = 163       # the f32-hostile value
    size = np.ones(m, np.int32)          # decoys: must be ignored
    bw = np.full(n_links, 1.0)
    lat = RNG.integers(0, 5_000, n_links).astype(np.int32)
    out = hub_route(jnp.asarray(send, jnp.int32), jnp.asarray(size),
                    jnp.asarray(link_id), jnp.asarray(bw, jnp.float32),
                    jnp.asarray(lat), ser_ns=jnp.asarray(ser),
                    block=block, interpret=True)
    ref = hub_visibility_ref(send, size, link_id, bw, lat, ser_ns=ser)
    np.testing.assert_array_equal(np.asarray(out, np.int64), ref)


def test_hub_visibility_ser_ns_bitexact():
    """The jnp scan path honors the same ser_ns bypass, bit-exactly."""
    from repro.core.engine_jax import hub_visibility

    m, n_links = 200, 4
    link_id = np.sort(RNG.integers(0, n_links, m)).astype(np.int32)
    send = np.zeros(m, np.int64)
    for l in range(n_links):
        idx = np.where(link_id == l)[0]
        send[idx] = np.sort(RNG.integers(0, 50_000, len(idx)))
    ser = RNG.integers(0, 10_000, m).astype(np.int32)
    lat = RNG.integers(0, 5_000, n_links).astype(np.int32)
    out = hub_visibility(jnp.asarray(send, jnp.int32),
                         jnp.ones(m, jnp.int32), jnp.asarray(link_id),
                         jnp.ones(n_links, jnp.float32),
                         jnp.asarray(lat), ser_ns=jnp.asarray(ser))
    ref = hub_visibility_ref(send, np.ones(m, np.int32), link_id,
                             np.ones(n_links), lat, ser_ns=ser)
    np.testing.assert_array_equal(np.asarray(out, np.int64), ref)


def test_hub_route_float32_mantissa_demo():
    """Regression pin for the bug the bypass fixes: a 163 ns
    serialization at 1 GB/ns-scale bandwidth truncates to 162 under
    the float32 path, and stays 163 under ser_ns."""
    send = jnp.zeros(1, jnp.int32)
    size = jnp.asarray([163], jnp.int32)
    link = jnp.zeros(1, jnp.int32)
    bw = jnp.asarray([1e9], jnp.float32)
    lat = jnp.zeros(1, jnp.int32)
    f32 = int(hub_route(send, size, link, bw, lat, interpret=True)[0])
    exact = int(hub_route(send, size, link, bw, lat,
                          ser_ns=jnp.asarray([163], jnp.int32),
                          interpret=True)[0])
    assert f32 == 162 and exact == 163
