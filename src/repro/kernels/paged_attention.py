"""Paged GQA attention — vLLM-style PagedAttention in Pallas.

The KV cache lives in pools of fixed-size pages; each request owns a
block table mapping its logical token positions to physical pages.  Two
kernels share the pool layout:

- **decode** (:func:`paged_decode_attention`): one new query token per
  request attends to its full (paged) history.  Grid =
  (batch, pages_per_seq) with the page dimension sequential.
- **chunked prefill** (:func:`paged_prefill_attention`): a whole prompt
  chunk of C query tokens for a *single* request attends causally to
  the already-paged history plus the in-chunk segment (the chunk's own
  K/V are scattered into the pool before the call, so the kernel only
  ever reads pages).  Grid = (lane groups, ctx_pages), pages
  sequential: a step reads one lane group's columns of one page.

**Pool layout.**  A pool is stored as the model step holds it, stacked
over layers: ``(L, P, page_size, K·hd)`` — a page is ``page_size`` rows
of the K/V projection's own width, whose default TPU layout is
row-major (a ``(…, K, hd)`` tail with ``hd = 64`` is not: the compiler
makes the page axis the lane axis there).  The block table, the lengths
and the layer index ride in scalar prefetch and the K/V *index maps
read them*, so each grid step DMAs exactly one ``(page_size, K·hd)``
page of one layer straight from the stored pool — no transpose, no
reshape and no gather of the pool ever runs.

Heads are handled in *lane groups*: the fewest kv heads whose columns
fill whole 128-lane tiles (2 heads at ``hd = 64``, 1 at ``hd = 128``;
all heads when the row is narrower), so every slice is lane-aligned.
A decode step holds a whole page and loops over its groups in VMEM; a
prefill step, whose C·g query rows per head are many, takes one group.  The queries of a group arrive block-diagonal —
head ``i``'s rows carry its query in head ``i``'s columns and zeros
elsewhere — so one matmul against the group's page columns gives each
head its own scores, and each head's output is the diagonal block of
the group's accumulator (the off-diagonal blocks are discarded outside
the kernel).  Flash-style running max/sum scratch accumulates across
pages, per head, in float32.

Pages past a request's length are skipped (the DMA still runs — index
maps are unconditional — but the FLOPs and the accumulator update are
predicated off, and freed/garbage page contents are masked to ±NEG_INF /
zero so recycled pages can never leak into another request's output).

**Quantized pages**: both kernels take optional per-page scale pools
(``(L, P, 1, page_size·K)`` float32 — one symmetric scale per token
slot per KV head, the ``(page_size, K)`` block of a page flattened
slot-major into one lane row, whose default layout is row-major).  The
scales are applied inside the kernel right after the page DMA — to a
head's scores for K and to its softmax weights for V, which equals
dequantizing the page (``int8 → f32 × scale``) — so the pool stays int8
in HBM and effective KV capacity per byte roughly quadruples versus
fp32 pages.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANES = 128


def _heads_per_group(n_kv_heads: int, hd: int, hd_v: int) -> int:
    """The fewest kv heads whose K and V columns fill whole lane tiles.

    All ``n_kv_heads`` when no proper divisor does (a row narrower than
    one tile, or a head width that never lines up).
    """
    for g in range(1, n_kv_heads):
        if n_kv_heads % g == 0 and (g * hd) % LANES == 0 and (g * hd_v) % LANES == 0:
            return g
    return n_kv_heads


def _group_queries(q: jax.Array, heads: int) -> jax.Array:
    """``(N, K, R, hd)`` queries -> block-diagonal ``(N, K/G, G·R, G·hd)``.

    Row ``i·R + r`` of a group holds head ``i``'s query row ``r`` in
    columns ``i·hd .. (i+1)·hd`` and zeros elsewhere.
    """
    N, K, R, hd = q.shape
    qg = q.reshape(N, K // heads, heads, R, 1, hd)
    eye = jnp.eye(heads, dtype=bool)[:, None, :, None]           # (G,1,G,1)
    blk = jnp.where(eye, qg, jnp.zeros((), q.dtype))              # (N,n,G,R,G,hd)
    return blk.reshape(N, K // heads, heads * R, heads * hd)


def _ungroup(o: jax.Array, heads: int, hd_v: int) -> jax.Array:
    """Inverse of :func:`_group_queries` on outputs: ``(N, K, R, hd_v)``.

    Head ``i``'s output is the ``(i, i)`` block of its group's rows and
    columns; the other blocks mix heads and are dropped.
    """
    N, n, GR, _ = o.shape
    o = o.reshape(N, n, heads, GR // heads, heads, hd_v)
    o = jnp.diagonal(o, axis1=2, axis2=4)                         # (N,n,R,hd_v,G)
    return jnp.moveaxis(o, -1, 2).reshape(N, n * heads, GR // heads, hd_v)


def _row_scales(sc: jax.Array, first_head: int, heads: int, rows: int,
                n_kv_heads: int) -> jax.Array:
    """Per-row scales of one lane group: ``(G·rows, page_size)``.

    ``sc`` is a page's ``(1, page_size·K)`` slot-major scale row; row
    ``i·rows + r`` gets head ``first_head + i``'s scale of every slot.
    The gather is a 0/1 selection matmul at full float32 precision, so
    it copies the scales exactly.
    """
    n = sc.shape[-1]
    ps = n // n_kv_heads
    shape = (heads * rows, n)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    head = first_head + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // rows
    mine = (col - (col // n_kv_heads) * n_kv_heads) == head
    picked = jnp.where(mine, jnp.broadcast_to(sc, shape), 0.0)
    slot_of = jax.lax.broadcasted_iota(jnp.int32, (n, ps), 0) // n_kv_heads
    fold = (slot_of == jax.lax.broadcasted_iota(jnp.int32, (n, ps), 1))
    return jax.lax.dot_general(
        picked, fold.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _attend_group(
    q_ref,        # (G·R, G·hd) block-diagonal queries of one lane group
    k,            # (page_size, G·hd) the group's columns of one page
    v,            # (page_size, G·hd_v)
    ks_ref,       # (1, page_size·K) f32 scale row of the page, or None
    vs_ref,
    m_ref,        # (G·R, 1)
    l_ref,        # (G·R, 1)
    acc_ref,      # (G·R, G·hd_v)
    *,
    qpos,         # (G·R, page_size) int32: each row's query position
    t_start,      # first token position of this page
    kv_len,       # positions >= kv_len hold no token of this request
    first_head,   # the group's first kv head
    scale: float,
    heads: int,
    n_kv_heads: int,
):
    """Fold one page into one lane group's running softmax."""
    rows = q_ref.shape[0] // heads
    q = q_ref[...].astype(jnp.float32) * scale
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    kpos = jax.lax.broadcasted_iota(jnp.int32, qpos.shape, 1) + t_start
    mask = kpos <= qpos
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                          # (G·R, ps)
    if ks_ref is not None:
        s = s * _row_scales(ks_ref[...], first_head, heads, rows, n_kv_heads)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # rows whose causal window hasn't started keep m == NEG_INF; exp(s-m)
    # would be exp(0)=1 there, so zero the masked weights explicitly
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    if vs_ref is not None:
        # a masked slot's scale may be garbage: 0 * NaN is NaN
        p = jnp.where(
            mask,
            p * _row_scales(vs_ref[...], first_head, heads, rows, n_kv_heads),
            0.0,
        )
    # sanitize rows past the context (p is 0 there, but 0*NaN = NaN)
    vrow = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + t_start
    v = jnp.where(vrow < kv_len, v, 0.0)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new


def _in_hbm(interpret: bool, *pools):
    """Pin pool operands to HBM, where the step program keeps them.

    The kernels DMA single pages, so the pools never need to be on-core;
    without the pin the compiler may stage a pool small enough to fit
    there (a scale pool, or a test-sized pool) whole, around the layer
    loop.  The interpreter has no memory spaces.
    """
    import jax.experimental.pallas.tpu as pltpu

    if interpret:
        return list(pools)
    return [pltpu.with_memory_space_constraint(p, pltpu.HBM) for p in pools]


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _paged_decode_kernel(
    bt_ref,       # (B, npp) int32 in SMEM — block tables
    len_ref,      # (B,) int32 in SMEM — valid lengths (incl. current token)
    layer_ref,    # (1,) int32 in SMEM — the pools' layer
    q_ref,        # (n_grp, G·g, G·hd) — one request's grouped queries
    k_ref,        # (page_size, K·hd) — the page this program visits
    v_ref,        # (page_size, K·hd_v)
    *rest,        # [ks_ref, vs_ref (1, page_size·K)] o_ref, scratch×3
    scale: float,
    heads: int,
    n_kv_heads: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    pi = pl.program_id(1)
    npp = pl.num_programs(1)
    length = len_ref[b]
    page_size = k_ref.shape[0]
    t_start = pi * page_size

    @pl.when(pi == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    # pages wholly beyond this request's history contribute nothing
    @pl.when(t_start < length)
    def _():
        # the one query sits at position length - 1
        qpos = jnp.full((q_ref.shape[1], page_size), length - 1, jnp.int32)
        n_grp, _, w = q_ref.shape
        w_v = acc_scr.shape[-1]
        for gi in range(n_grp):    # every group of the page, lane-aligned
            _attend_group(
                q_ref.at[gi], k_ref[:, gi * w:(gi + 1) * w],
                v_ref[:, gi * w_v:(gi + 1) * w_v], ks_ref, vs_ref,
                m_scr.at[gi], l_scr.at[gi], acc_scr.at[gi],
                qpos=qpos, t_start=t_start, kv_len=length,
                first_head=gi * heads, scale=scale, heads=heads,
                n_kv_heads=n_kv_heads,
            )

    @pl.when(pi == npp - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jax.Array,             # (B, H, hd)
    k_pages: jax.Array,       # (L, P, page_size, K·hd) stored page pools
    v_pages: jax.Array,       # (L, P, page_size, K·hd_v)
    block_tables: jax.Array,  # (B, pages_per_seq) int32 page ids
    lengths: jax.Array,       # (B,) int32 — valid tokens (incl. current)
    layer=0,                  # int32 scalar: which of the L pools
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,  # (L, P, 1, page_size·K) f32 (int8)
    v_scales: Optional[jax.Array] = None,
) -> jax.Array:
    quantized = k_scales is not None
    B, H, hd = q.shape
    page_size = k_pages.shape[2]
    K = k_pages.shape[3] // hd
    hd_v = v_pages.shape[3] // K
    npp = block_tables.shape[1]
    g = H // K
    G = _heads_per_group(K, hd, hd_v)
    n_grp = K // G
    scale = scale if scale is not None else hd ** -0.5

    qg = _group_queries(q.reshape(B, K, g, hd), G)       # (B, n_grp, G·g, G·hd)

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=scale,
        heads=G,
        n_kv_heads=K,
        quantized=quantized,
    )

    import jax.experimental.pallas.tpu as pltpu

    # the paged gather: the layer and page come from scalar prefetch
    page_spec = lambda b, j, bt, lens, lyr: (lyr[0], bt[b, j], 0, 0)  # noqa: E731
    row_spec = lambda b, j, bt, lens, lyr: (b, 0, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((None, n_grp, G * g, G * hd), row_spec),
        pl.BlockSpec((None, None, page_size, K * hd), page_spec),
        pl.BlockSpec((None, None, page_size, K * hd_v), page_spec),
    ]
    operands = [qg, *_in_hbm(interpret, k_pages, v_pages)]
    if quantized:
        in_specs += [pl.BlockSpec((None, None, 1, page_size * K), page_spec)] * 2
        operands += _in_hbm(interpret, k_scales, v_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (block_tables, lengths, layer)
        grid=(B, npp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, n_grp, G * g, G * hd_v), row_spec),
        scratch_shapes=[
            pltpu.VMEM((n_grp, G * g, 1), jnp.float32),
            pltpu.VMEM((n_grp, G * g, 1), jnp.float32),
            pltpu.VMEM((n_grp, G * g, G * hd_v), jnp.float32),
        ],
    )
    out_dtype = jnp.float32 if quantized else q.dtype
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_grp, G * g, G * hd_v), out_dtype),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32), *operands,
    )
    return _ungroup(out, G, hd_v).reshape(B, H, hd_v).astype(q.dtype)


def _paged_prefill_kernel(
    bt_ref,       # (npp,) int32 in SMEM — this request's block table
    layer_ref,    # (1,) int32 in SMEM — the pools' layer
    q_ref,        # (G·C·g, G·hd) — one lane group's query rows
    k_ref,        # (page_size, G·hd) — the group's columns of a page
    v_ref,        # (page_size, G·hd_v)
    *rest,        # [ks_ref, vs_ref (1, page_size·K)] o_ref, scratch×3
    scale: float,
    past: int,
    ctx: int,
    group: int,
    heads: int,
    n_kv_heads: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    gi = pl.program_id(0)
    pi = pl.program_id(1)
    npp = pl.num_programs(1)
    page_size = k_ref.shape[0]
    rows = q_ref.shape[0] // heads                           # C·g per head

    @pl.when(pi == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    # row i·C·g + r holds query token past + r//g of the group's head i
    r = jax.lax.broadcasted_iota(jnp.int32, (q_ref.shape[0], page_size), 0)
    qpos = (r - (r // rows) * rows) // group + past
    _attend_group(
        q_ref, k_ref[...], v_ref[...], ks_ref, vs_ref, m_scr, l_scr, acc_scr,
        qpos=qpos, t_start=pi * page_size, kv_len=ctx,
        first_head=gi * heads, scale=scale, heads=heads,
        n_kv_heads=n_kv_heads,
    )

    @pl.when(pi == npp - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


@functools.partial(
    jax.jit, static_argnames=("past", "scale", "interpret")
)
def paged_prefill_attention(
    q: jax.Array,            # (C, H, hd) — one request's chunk queries
    k_pages: jax.Array,      # (L, P, page_size, K·hd) stored page pools
    v_pages: jax.Array,      # (L, P, page_size, K·hd_v)
    block_table: jax.Array,  # (pages_per_seq,) int32 page ids
    past: int,               # prompt tokens already prefilled (chunk offset)
    layer=0,                 # int32 scalar: which of the L pools
    scale: Optional[float] = None,
    interpret: bool = False,
    k_scales: Optional[jax.Array] = None,  # (L, P, 1, page_size·K) f32 (int8)
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
    page_size = k_pages.shape[2]
    K = k_pages.shape[3] // hd
    hd_v = v_pages.shape[3] // K
    g = H // K
    G = _heads_per_group(K, hd, hd_v)
    n_grp = K // G
    ctx = past + C
    n_ctx_pages = -(-ctx // page_size)
    scale = scale if scale is not None else hd ** -0.5

    # (C, K, g, hd) -> per kv head, C·g rows in token-major order
    qh = q.reshape(C, K, g, hd).transpose(1, 0, 2, 3).reshape(1, K, C * g, hd)
    qg = _group_queries(qh, G)[0]                         # (n_grp, G·C·g, G·hd)

    kernel = functools.partial(
        _paged_prefill_kernel,
        scale=scale,
        past=past,
        ctx=ctx,
        group=g,
        heads=G,
        n_kv_heads=K,
        quantized=quantized,
    )

    import jax.experimental.pallas.tpu as pltpu

    # one grid step: one lane group's columns of one page (a lane-aligned
    # block), so the kernel body holds one group whatever K is
    group_cols = lambda gi, j, bt, lyr: (lyr[0], bt[j], 0, gi)  # noqa: E731
    page_row = lambda gi, j, bt, lyr: (lyr[0], bt[j], 0, 0)  # noqa: E731
    group_rows = lambda gi, j, bt, lyr: (gi, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((None, G * C * g, G * hd), group_rows),
        pl.BlockSpec((None, None, page_size, G * hd), group_cols),
        pl.BlockSpec((None, None, page_size, G * hd_v), group_cols),
    ]
    operands = [qg, *_in_hbm(interpret, k_pages, v_pages)]
    if quantized:
        in_specs += [pl.BlockSpec((None, None, 1, page_size * K), page_row)] * 2
        operands += _in_hbm(interpret, k_scales, v_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (block_table, layer)
        grid=(n_grp, n_ctx_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, G * C * g, G * hd_v), group_rows),
        scratch_shapes=[
            pltpu.VMEM((G * C * g, 1), jnp.float32),
            pltpu.VMEM((G * C * g, 1), jnp.float32),
            pltpu.VMEM((G * C * g, G * hd_v), jnp.float32),
        ],
    )
    out_dtype = jnp.float32 if quantized else q.dtype
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_grp, G * C * g, G * hd_v), out_dtype),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32), *operands,
    )
    out = _ungroup(out[None], G, hd_v)[0]                 # (K, C·g, hd_v)
    out = out.reshape(K, C, g, hd_v).transpose(1, 0, 2, 3)
    return out.reshape(C, H, hd_v).astype(q.dtype)


def _write_scales_kernel(
    page_ref,     # (T,) int32 in SMEM — each token's physical page
    slot_ref,     # (T,) int32 in SMEM — each token's slot in its page
    layer_ref,    # (1,) int32 in SMEM
    vals_ref,     # (T, page_size·K) — token t's K scales tiled over the row
    row_ref,      # (1, page_size·K) — token t's page row as stored
    o_ref,        # (1, page_size·K) — the same row, aliased to the pool
    *,
    n_kv_heads: int,
):
    t = pl.program_id(0)
    # consecutive tokens of one page share the output block: start from
    # the stored row only when the page changes
    fresh = (t == 0) | (page_ref[t] != page_ref[jnp.maximum(t - 1, 0)])

    @pl.when(fresh)
    def _():
        o_ref[...] = row_ref[...]

    col = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    mine = col // n_kv_heads == slot_ref[t]
    o_ref[...] = jnp.where(mine, vals_ref[pl.ds(t, 1), :], o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_scales(
    scales: jax.Array,   # (L, P, 1, page_size·K) f32 stored scale pool
    layer,               # int32 scalar
    pages: jax.Array,    # (T,) int32 — tokens of one page are consecutive
    slots: jax.Array,    # (T,) int32
    values: jax.Array,   # (T, K) f32 — each token's per-head scales
    interpret: bool = False,
) -> jax.Array:
    """Write T tokens' scales into a stored scale pool, in place.

    Touches only the page rows the tokens land in: one grid step per
    token reads its page's row, sets the token's ``K`` entries and
    writes the row back into the aliased pool.  (An XLA scatter into
    this pool makes the compiler stage the whole pool through on-chip
    memory around the layer loop.)  A page's tokens must be consecutive
    in ``pages``, as a prefill chunk's and a decode batch's are.
    """
    T, K = values.shape
    n = scales.shape[-1]
    tiled = jnp.tile(values.astype(scales.dtype), (1, n // K))  # lane c: head c % K

    import jax.experimental.pallas.tpu as pltpu

    row = pl.BlockSpec(
        (None, None, 1, n), lambda t, pg, sl, lyr: (lyr[0], pg[t], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (pages, slots, layer)
        grid=(T,),
        in_specs=[pl.BlockSpec((T, n), lambda t, pg, sl, lyr: (0, 0)), row],
        out_specs=row,
    )
    return pl.pallas_call(
        functools.partial(_write_scales_kernel, n_kv_heads=K),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct if interpret else pltpu.HBM)(
            scales.shape, scales.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(
        pages.astype(jnp.int32), slots.astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32), tiled, *_in_hbm(interpret, scales),
    )


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
        One layer's per-page scale pools (float32), in the
        :func:`~repro.kernels.ref.scale_view` of the stored layout.
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
