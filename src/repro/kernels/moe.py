"""Pallas TPU kernel of an expert layer's shared experts' SwiGLU
(``moe_shared``).

The kernel is named, and the name reaches the profiler's op events, so
the benchmark can find and time it; the held experts' grouped matmuls
are ``jax.lax.ragged_dot``, which the TPU compiler runs as its own named
``ragged-dot`` op.  ``common.mlp_forward`` is the jnp form the kernel
must equal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def row_block(n: int, cap: int) -> int:
    """Largest multiple of 8 up to ``cap`` that divides ``n`` (a block
    of rows that tiles), else ``n`` itself (one block of the whole)."""
    fits = [d for d in range(8, min(n, cap) + 1, 8) if n % d == 0]
    return max(fits) if fits else n


def _swiglu_kernel(x_ref, g_ref, u_ref, d_ref, o_ref, acc_ref, *, n_f):
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
    h = g * (1.0 / (1.0 + jnp.exp(-g))) * u
    acc_ref[...] += jnp.dot(h.astype(x.dtype), d_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_shared(x, w_gate, w_up, w_down, *, interpret: bool = False):
    """SwiGLU ``(silu(x @ w_gate) * (x @ w_up)) @ w_down`` of x (T, D);
    weights (D, F), (D, F), (F, D).  Blocks of rows times blocks of the
    width, the down projection accumulated in float32 over the width."""
    t, d = x.shape
    f = w_gate.shape[1]
    tb, bf = row_block(t, 256), (256 if f % 256 == 0 else f)
    n_f = f // bf
    return pl.pallas_call(
        functools.partial(_swiglu_kernel, n_f=n_f),
        grid=(t // tb, n_f),
        in_specs=[pl.BlockSpec((tb, d), lambda i, j: (i, 0)),
                  pl.BlockSpec((d, bf), lambda i, j: (0, j)),
                  pl.BlockSpec((d, bf), lambda i, j: (0, j)),
                  pl.BlockSpec((bf, d), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((tb, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((tb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_shared",
    )(x, w_gate, w_up, w_down)
