"""Mamba selective-scan Pallas kernel (chunked recurrence).

The recurrence h_t = exp(Δ_t·A)⊙h_{t-1} + (Δ_t·x_t)·B_t is sequential in
t, so the TPU-native layout makes t the innermost (sequential) grid dim in
chunks while (batch, channel-block) parallelize the outer grid.  The state
h (d_block, N) lives in VMEM scratch across the whole t-sweep — it never
touches HBM between chunks, which is the entire point: the GPU version
leans on warp-level scans in SRAM, the TPU version keeps the carried state
VMEM-resident and streams only x/Δ/B/C tiles.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssm_kernel(
    x_ref,    # (1, chunk, d_blk)
    dt_ref,   # (1, chunk, d_blk)
    a_ref,    # (d_blk, N)
    b_ref,    # (1, chunk, N)
    c_ref,    # (1, chunk, N)
    dskip_ref,  # (d_blk,)
    h0_ref,   # (1, d_blk, N)
    y_ref,    # (1, chunk, d_blk)
    hT_ref,   # (1, d_blk, N)
    h_scr,    # (d_blk, N) VMEM carry
    *,
    chunk: int,
):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)        # (chunk, d_blk)
    dt = dt_ref[0].astype(jnp.float32)
    A = a_ref[...].astype(jnp.float32)      # (d_blk, N)
    Bm = b_ref[0].astype(jnp.float32)       # (chunk, N)
    Cm = c_ref[0].astype(jnp.float32)

    def step(t, carry):
        h = carry
        dA = jnp.exp(dt[t][:, None] * A)                  # (d_blk, N)
        h = dA * h + (dt[t] * x[t])[:, None] * Bm[t][None, :]
        y = jnp.sum(h * Cm[t][None, :], axis=1)           # (d_blk,)
        y_ref[0, t, :] = (
            y + x[t] * dskip_ref[...].astype(jnp.float32)
        ).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ti == nt - 1)
    def _out():
        hT_ref[0] = h_scr[...].astype(hT_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "d_block", "interpret")
)
def ssm_scan(
    x: jax.Array,       # (B, T, D)
    dt: jax.Array,      # (B, T, D)
    A: jax.Array,       # (D, N)
    Bm: jax.Array,      # (B, T, N)
    Cm: jax.Array,      # (B, T, N)
    D: jax.Array,       # (D,)
    h0: Optional[jax.Array] = None,
    chunk: int = 128,
    d_block: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, T, Dd = x.shape
    N = A.shape[1]
    chunk = min(chunk, T)
    d_block = min(d_block, Dd)
    if T % chunk or Dd % d_block:
        # fall back to the oracle for ragged shapes
        from .ref import ssm_scan_ref

        return ssm_scan_ref(x, dt, A, Bm, Cm, D, h0=h0)
    h0 = (
        h0.astype(jnp.float32)
        if h0 is not None
        else jnp.zeros((B, Dd, N), jnp.float32)
    )
    nd = Dd // d_block
    nt = T // chunk

    y, hT = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, chunk, d_block), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((d_block, N), lambda b, d, t: (d, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((d_block,), lambda b, d, t: (d,)),
            pl.BlockSpec((1, d_block, N), lambda b, d, t: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, d_block, N), lambda b, d, t: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, Dd), x.dtype),
            jax.ShapeDtypeStruct((B, Dd, N), jnp.float32),
        ],
        scratch_shapes=[_vmem((d_block, N))],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(x, dt, A, Bm, Cm, D, h0)
    return y, hT


def _vmem(shape):
    import jax.experimental.pallas.tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params():
    import jax.experimental.pallas.tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )
