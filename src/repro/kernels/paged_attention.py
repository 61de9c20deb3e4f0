"""Paged GQA attention — vLLM-style PagedAttention in Pallas.

The KV cache lives in a pool of fixed-size pages; each request owns a
block table mapping its logical token positions to physical pages.  Two
kernels share the pool layout:

- **decode** (:func:`paged_decode_attention`): one new query token per
  request attends to its full (paged) history.  Grid =
  (batch · kv_head, pages_per_seq) with the page dimension sequential.
- **chunked prefill** (:func:`paged_prefill_attention`): a whole prompt
  chunk of C query tokens for a *single* request attends causally to
  the already-paged history plus the in-chunk segment (the chunk's own
  K/V are scattered into the pool before the call, so the kernel only
  ever reads pages).  Grid = (kv_head, ctx_pages), pages sequential.

In both, the block table rides in scalar prefetch and the K/V *index
maps read the block table*, so each program DMAs exactly one physical
page — the gather never materializes a dense cache.  Flash-style running
max/sum scratch accumulates across pages, and the whole q-head group is
processed per program so every page is streamed HBM→VMEM exactly once
for all grouped heads.

Pages past a request's length are skipped (the DMA still runs — index
maps are unconditional — but the FLOPs and the accumulator update are
predicated off, and freed/garbage page contents are masked to ±NEG_INF /
zero so recycled pages can never leak into another request's output).

**Quantized pages**: both kernels take optional per-page scale pools
(``(P, page_size, K)`` float32 — one symmetric scale per token slot per
KV head, stored page-major alongside the int8 K/V pools).  Scales are
dequantized *inside* the kernel (``int8 → f32 × scale``) right after the
page DMA, so the pool stays int8 in HBM and effective KV capacity per
byte roughly quadruples versus fp32 pages.

Layout note: pools are stored token-major, ``(P, page_size, K, hd)`` —
the layout the engine's scatter-writes want — and transposed to
``(K, P, page_size, hd)`` at call time so the kernel's trailing two dims
are (page_size, head_dim), which tiles cleanly on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _paged_decode_kernel(
    bt_ref,       # (B, npp) int32 in SMEM — block tables
    len_ref,      # (B,) int32 in SMEM — valid lengths (incl. current token)
    q_ref,        # (1, g, hd)
    k_ref,        # (1, 1, page_size, hd) — the page this program visits
    v_ref,        # (1, 1, page_size, hd_v)
    *rest,        # [ks_ref, vs_ref (1, 1, page_size, 1)] o_ref, scratch×3
    scale: float,
    page_size: int,
    n_kv_heads: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    bh = pl.program_id(0)
    pi = pl.program_id(1)
    npp = pl.num_programs(1)
    b = bh // n_kv_heads
    length = len_ref[b]
    t_start = pi * page_size

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale              # (g, hd)
        k = k_ref[0, 0].astype(jnp.float32)                   # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)                   # (ps, hd_v)
        if quantized:
            k = k * ks_ref[0, 0]                              # (ps, 1) bcast
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # (g, ps)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + t_start
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # sanitize rows past `length` (p is 0 there, but 0*NaN = NaN)
        vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + t_start
        v = jnp.where(vrow < length, v, 0.0)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    # pages wholly beyond this request's history contribute nothing
    pl.when(t_start < length)(_accumulate)

    @pl.when(pi == npp - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jax.Array,             # (B, H, hd)
    k_pages: jax.Array,       # (P, page_size, K, hd) physical page pool
    v_pages: jax.Array,       # (P, page_size, K, hd_v)
    block_tables: jax.Array,  # (B, pages_per_seq) int32 page ids
    lengths: jax.Array,       # (B,) int32 — valid tokens (incl. current)
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,  # (P, page_size, K) f32 (int8 pools)
    v_scales: Optional[jax.Array] = None,
) -> jax.Array:
    quantized = k_scales is not None
    B, H, hd = q.shape
    P, page_size, K, hd_v = (
        k_pages.shape[0], k_pages.shape[1], k_pages.shape[2], v_pages.shape[3]
    )
    npp = block_tables.shape[1]
    g = H // K
    scale = scale if scale is not None else hd ** -0.5

    qr = q.reshape(B, K, g, hd).reshape(B * K, g, hd)
    kr = k_pages.transpose(2, 0, 1, 3)   # (K, P, ps, hd)
    vr = v_pages.transpose(2, 0, 1, 3)   # (K, P, ps, hd_v)

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=scale,
        page_size=page_size,
        n_kv_heads=K,
        quantized=quantized,
    )

    import jax.experimental.pallas.tpu as pltpu

    page_spec = lambda bh, j, bt, lens: (bh % K, bt[bh // K, j], 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, g, hd), lambda bh, j, bt, lens: (bh, 0, 0)),
        # the paged gather: the page index comes from the block table
        pl.BlockSpec((1, 1, page_size, hd), page_spec),
        pl.BlockSpec((1, 1, page_size, hd_v), page_spec),
    ]
    operands = [qr, kr, vr]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page_size, 1), page_spec)] * 2
        operands += [
            k_scales.transpose(2, 0, 1).reshape(K, P, page_size, 1),
            v_scales.transpose(2, 0, 1).reshape(K, P, page_size, 1),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (block_tables, lengths)
        grid=(B * K, npp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, hd_v), lambda bh, j, bt, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd_v), jnp.float32),
        ],
    )
    out_dtype = jnp.float32 if quantized else q.dtype
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * K, g, hd_v), out_dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    return out.reshape(B, K, g, hd_v).reshape(B, H, hd_v).astype(q.dtype)


def _paged_prefill_kernel(
    bt_ref,       # (npp,) int32 in SMEM — this request's block table
    q_ref,        # (1, C·g, hd) — all grouped query rows for one kv head
    k_ref,        # (1, 1, page_size, hd)
    v_ref,        # (1, 1, page_size, hd_v)
    *rest,        # [ks_ref, vs_ref (1, 1, page_size, 1)] o_ref, scratch×3
    scale: float,
    page_size: int,
    past: int,
    ctx: int,
    group: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    pi = pl.program_id(1)
    npp = pl.num_programs(1)
    t_start = pi * page_size

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale                  # (C·g, hd)
    k = k_ref[0, 0].astype(jnp.float32)                       # (ps, hd)
    v = v_ref[0, 0].astype(jnp.float32)                       # (ps, hd_v)
    if quantized:
        k = k * ks_ref[0, 0]                                  # (ps, 1) bcast
        v = v * vs_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # (C·g, ps)
    # row r holds query token past + r//g; causal + context masking in one
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group + past
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + t_start
    mask = kpos <= qpos
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # rows whose causal window hasn't started keep m == NEG_INF; exp(s-m)
    # would be exp(0)=1 there, so zero the masked weights explicitly
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    # sanitize rows past the context (p is 0 there, but 0*NaN = NaN)
    vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + t_start
    v = jnp.where(vrow < ctx, v, 0.0)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(pi == npp - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("past", "scale", "interpret")
)
def paged_prefill_attention(
    q: jax.Array,            # (C, H, hd) — one request's chunk queries
    k_pages: jax.Array,      # (P, page_size, K, hd) physical page pool
    v_pages: jax.Array,      # (P, page_size, K, hd_v)
    block_table: jax.Array,  # (pages_per_seq,) int32 page ids
    past: int,               # prompt tokens already prefilled (chunk offset)
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,  # (P, page_size, K) f32 (int8 pools)
    v_scales: Optional[jax.Array] = None,
) -> jax.Array:
    """Fused chunked-prefill attention over block tables.

    The chunk's K/V must already be scattered into the pools (positions
    ``past .. past+C``); its queries attend causally to the
    ``ceil((past+C)/page_size)`` context pages named by the block table.
    Returns ``(C, H, hd_v)``.
    """
    quantized = k_scales is not None
    C, H, hd = q.shape
    P, page_size, K, hd_v = (
        k_pages.shape[0], k_pages.shape[1], k_pages.shape[2], v_pages.shape[3]
    )
    g = H // K
    ctx = past + C
    n_ctx_pages = -(-ctx // page_size)
    scale = scale if scale is not None else hd ** -0.5

    qr = q.reshape(C, K, g, hd).transpose(1, 0, 2, 3).reshape(K, C * g, hd)
    kr = k_pages.transpose(2, 0, 1, 3)   # (K, P, ps, hd)
    vr = v_pages.transpose(2, 0, 1, 3)   # (K, P, ps, hd_v)

    kernel = functools.partial(
        _paged_prefill_kernel,
        scale=scale,
        page_size=page_size,
        past=past,
        ctx=ctx,
        group=g,
        quantized=quantized,
    )

    import jax.experimental.pallas.tpu as pltpu

    page_spec = lambda kk, j, bt: (kk, bt[j], 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, C * g, hd), lambda kk, j, bt: (kk, 0, 0)),
        pl.BlockSpec((1, 1, page_size, hd), page_spec),
        pl.BlockSpec((1, 1, page_size, hd_v), page_spec),
    ]
    operands = [qr, kr, vr]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, page_size, 1), page_spec)] * 2
        operands += [
            k_scales.transpose(2, 0, 1).reshape(K, P, page_size, 1),
            v_scales.transpose(2, 0, 1).reshape(K, P, page_size, 1),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # (block_table,)
        grid=(K, n_ctx_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, C * g, hd_v), lambda kk, j, bt: (kk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C * g, 1), jnp.float32),
            pltpu.VMEM((C * g, 1), jnp.float32),
            pltpu.VMEM((C * g, hd_v), jnp.float32),
        ],
    )
    out_dtype = jnp.float32 if quantized else q.dtype
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((K, C * g, hd_v), out_dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), *operands)
    out = out.reshape(K, C, g, hd_v).transpose(1, 0, 2, 3)
    return out.reshape(C, H, hd_v).astype(q.dtype)


def check_block_table_bounds(
    block_tables,
    lengths,
    num_pages: int,
    page_size: int,
    trash_page: int = 0,
) -> None:
    """Host-side static bounds check of a decode call's block tables.

    The Pallas kernel's index maps are *unconditional*: every
    ``bt[b, j]`` entry is used as a DMA source page, valid or not.
    Out-of-range indices would read (and the engine's scatter-write
    would write) outside the pool, and a trash entry inside a row's
    covered range means a live token's KV was never given a real page.
    This check runs on the host arrays immediately before the kernel
    (under ``REPRO_SANITIZE``/``sanitize=True``) and in unit tests with
    adversarial tables.

    Parameters
    ----------
    block_tables : array_like, shape (B, pages_per_seq)
        Physical page ids per row (``trash_page`` marks padding).
    lengths : array_like, shape (B,)
        Valid tokens per row *excluding* the token being decoded (the
        engine's convention: the incoming token writes at position
        ``lengths[b]``); 0 marks a padding row.
    num_pages : int
        The allocator's pool size.
    page_size : int
        Tokens per page.
    trash_page : int, optional
        The reserved padding page id.

    Raises
    ------
    ValueError
        Naming the offending row/entry on any out-of-range index or
        any trash entry within a live row's covered page range.
    """
    import numpy as np

    bt = np.asarray(block_tables)
    lens = np.asarray(lengths)
    if bt.ndim != 2 or lens.shape != (bt.shape[0],):
        raise ValueError(
            f"shape mismatch: block_tables {bt.shape} vs lengths {lens.shape}"
        )
    bad = (bt < 0) | (bt >= num_pages)
    if bad.any():
        b, j = map(int, np.argwhere(bad)[0])
        raise ValueError(
            f"block-table entry out of pool bounds: bt[{b}, {j}] = "
            f"{int(bt[b, j])} not in [0, {num_pages})"
        )
    # a live row writes at position lengths[b]: pages 0..lengths[b]//ps
    # inclusive must be real pages
    cov = np.where(lens > 0, lens // page_size + 1, 0)
    if (cov > bt.shape[1]).any():
        b = int(np.argmax(cov > bt.shape[1]))
        raise ValueError(
            f"row {b} needs {int(cov[b])} pages for length {int(lens[b])} "
            f"but the block table holds only {bt.shape[1]}"
        )
    pos = np.arange(bt.shape[1])[None, :]
    covered_trash = (pos < cov[:, None]) & (bt == trash_page)
    if covered_trash.any():
        b, j = map(int, np.argwhere(covered_trash)[0])
        raise ValueError(
            f"trash page inside covered range: bt[{b}, {j}] is the trash "
            f"page but row {b} has length {int(lens[b])} "
            f"(covers {int(cov[b])} pages)"
        )


def check_scale_pool_finite(
    k_scales,
    v_scales,
    block_tables,
    lengths,
    page_size: int,
) -> None:
    """Host-side check that quantized pages' scales are finite and positive.

    A corrupted scale entry (NaN/inf/non-positive) inside a live row's
    covered range would poison every logit that touches the page — and
    unlike garbage K/V *values* (masked to softmax weight 0), a bad
    scale multiplies *valid* dequantized history.  Runs on host arrays
    under ``REPRO_SANITIZE``/``sanitize=True`` alongside
    :func:`check_block_table_bounds`.

    Parameters
    ----------
    k_scales, v_scales : array_like, shape (P, page_size, K)
        Per-page scale pools (float32).
    block_tables : array_like, shape (B, pages_per_seq)
        Physical page ids per row.
    lengths : array_like, shape (B,)
        Valid tokens per row *excluding* the token being decoded.
    page_size : int
        Tokens per page.

    Raises
    ------
    ValueError
        Naming the offending (row, page, slot) on the first bad scale
        covering a live token.
    """
    import numpy as np

    bt = np.asarray(block_tables)
    lens = np.asarray(lengths)
    for name, scales in (("k_scales", k_scales), ("v_scales", v_scales)):
        sc = np.asarray(scales)
        bad = ~np.isfinite(sc) | (sc <= 0)
        if not bad.any():
            continue
        # bad entries only matter where a live token's KV lives
        for b in range(bt.shape[0]):
            n = int(lens[b])
            for t in range(n):
                page, slot = int(bt[b, t // page_size]), t % page_size
                if bad[page, slot].any():
                    kh = int(np.argmax(bad[page, slot]))
                    raise ValueError(
                        f"{name}[{page}, {slot}, {kh}] = "
                        f"{float(sc[page, slot, kh])!r} covers live token "
                        f"{t} of row {b}: scales must be finite and > 0"
                    )
