"""Fused RMSNorm Pallas kernel.

Single HBM pass: each program normalizes a (block_rows, d) tile in VMEM —
mean-of-squares, rsqrt, scale by gamma — instead of the 3-pass unfused
jnp version (square+mean, rsqrt, multiply).  Rows = flattened (B, S).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * g_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(
    x: jax.Array,
    gamma: jax.Array,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, gamma)
    return out.reshape(orig_shape)
