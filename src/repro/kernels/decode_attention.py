"""GQA decode attention over a slot KV cache — the serving hot spot.

One new query token per request attends to its full cache.  Decode is
memory-bound (the cache streams HBM→VMEM once), so the kernel's job is to
keep that stream dense: grid = (batch·kv_head, kv_blocks) with the kv
dimension sequential, flash-style running max/sum in VMEM scratch, and the
whole q-head group (g rows) processed per program so each cache block is
read exactly once for all grouped heads.

Per-request valid lengths are applied inside the kernel (slot caches are
allocated at S_max), prefetching `lengths` as a scalar operand.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_kernel(
    len_ref,      # (B,) int32 in SMEM — valid cache lengths
    q_ref,        # (1, g, hd)
    k_ref,        # (1, block_k, hd)
    v_ref,        # (1, block_k, hd_v)
    o_ref,        # (1, g, hd_v)
    m_scr,        # (g, 1)
    l_scr,        # (g, 1)
    acc_scr,      # (g, hd_v)
    *,
    scale: float,
    block_k: int,
    n_kv_heads: int,
):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    b = bh // n_kv_heads
    length = len_ref[b]
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale           # (g, hd)
        k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # (g, bk)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        # sanitize padded tail rows (p is 0 there, but 0*NaN = NaN)
        vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + k_start
        v = jnp.where(vrow < length, v, 0.0)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    # skip cache blocks entirely beyond this request's length
    pl.when(k_start < length)(_accumulate)

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,            # (B, H, hd)
    k_cache: jax.Array,      # (B, S, K, hd)
    v_cache: jax.Array,      # (B, S, K, hd_v)
    lengths: jax.Array,      # (B,) int32
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    _, S, K, hd_v = (
        k_cache.shape[0], k_cache.shape[1], k_cache.shape[2], v_cache.shape[3]
    )
    g = H // K
    scale = scale if scale is not None else hd ** -0.5
    block_k = min(block_k, S)
    nk = pl.cdiv(S, block_k)

    qr = q.reshape(B, K, g, hd).reshape(B * K, g, hd)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(B * K, S, hd)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(B * K, S, hd_v)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, n_kv_heads=K
    )

    import jax.experimental.pallas.tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * K, nk),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda b, j, lens: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, lens: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd_v), lambda b, j, lens: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd_v), lambda b, j, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd_v), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * K, g, hd_v), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kr, vr)
    return out.reshape(B, K, g, hd_v).reshape(B, H, hd_v)
