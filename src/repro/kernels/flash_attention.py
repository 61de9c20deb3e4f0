"""Flash attention (prefill/train) as a Pallas TPU kernel.

TPU adaptation of the FlashAttention tiling: the grid walks
(batch·kv_head, q_blocks, kv_blocks) with the kv dimension innermost and
sequential ("arbitrary" dimension semantics), carrying running max / sum /
accumulator in VMEM scratch.  Block shapes are MXU-aligned (multiples of
128 on the lane dim, head_dim padded by BlockSpec).  GQA is handled by
folding the q-head group into the q rows of each (batch, kv_head) program
so the MXU sees (block_q·group, head_dim) @ (head_dim, block_k) matmuls.

Causal masking skips fully-masked kv blocks via a grid predicate (the
`when` guard on the accumulation), matching the memory-bandwidth win of
the original paper on the TPU memory hierarchy (HBM→VMEM instead of
HBM→SRAM).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _attn_kernel(
    q_ref,        # (1, block_q * g, hd)
    k_ref,        # (1, block_k, hd)
    v_ref,        # (1, block_k, hd_v)
    o_ref,        # (1, block_q * g, hd_v)
    m_scr,        # (block_q * g, 1) running max
    l_scr,        # (block_q * g, 1) running sum
    acc_scr,      # (block_q * g, hd_v)
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    g: int,
    kv_len: Optional[int],
    s_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level causal skip: kv block strictly after the q block's end
    q_start = qi * block_q                       # token rows (pre-group)
    k_start = ki * block_k

    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq*g, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # (bq*g, bk)
        # causal mask at token granularity
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g + q_start
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
        s = jnp.where(cols < s_k, s, NEG_INF)   # ragged tail (block padding)
        if causal:
            s = jnp.where(cols <= rows, s, NEG_INF)
        if kv_len is not None:
            s = jnp.where(cols < kv_len, s, NEG_INF)

        m_prev = m_scr[...]                                # (bq*g, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (bq*g, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                   # (bk, hd_v)
        # sanitize padded tail rows of v (uninitialized block padding):
        # p is 0 there but 0*NaN = NaN, so replace via where
        vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + k_start
        v = jnp.where(vrow < s_k, v, 0.0)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    if causal:
        # skip kv blocks that start beyond the last row of this q block
        q_last = q_start + block_q - 1
        pl.when(k_start <= q_last)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "q_offset", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(
    q: jax.Array,                 # (B, S_q, H, hd)
    k: jax.Array,                 # (B, S_k, K, hd)
    v: jax.Array,                 # (B, S_k, K, hd_v)
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pallas flash attention with GQA; matches kernels/ref.attention_ref."""
    B, Sq, H, hd = q.shape
    _, Sk, K, hd_v = k.shape[0], k.shape[1], k.shape[2], v.shape[3]
    g = H // k.shape[2]
    K = k.shape[2]
    scale = scale if scale is not None else hd ** -0.5
    if kv_len is not None:
        raise NotImplementedError("per-batch kv_len: use ops.attention impl='ref'")
    if causal and q_offset != 0:
        raise NotImplementedError("q_offset with causal prefill not needed here")

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)

    # layout: fold heads into the grid; group dim rides with q rows
    # q -> (B*K, Sq*g, hd) with rows ordered [token-major, group-minor]
    qr = q.reshape(B, Sq, K, g, hd).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(B * K, Sq * g, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd_v)

    kernel = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        g=g,
        kv_len=None,
        s_k=Sk,
    )

    out = pl.pallas_call(
        kernel,
        grid=(B * K, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q * g, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd_v), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q * g, hd_v), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K, Sq * g, hd_v), q.dtype),
        scratch_shapes=[
            _vmem((block_q * g, 1)),
            _vmem((block_q * g, 1)),
            _vmem((block_q * g, hd_v)),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qr, kr, vr)

    out = out.reshape(B, K, Sq, g, hd_v).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Sq, H, hd_v)


def _vmem(shape):
    import jax.experimental.pallas.tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _compiler_params():
    import jax.experimental.pallas.tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )
