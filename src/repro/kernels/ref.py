"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantics ground truth: kernels/tests assert allclose
against these, and they double as the portable CPU path used by smoke
tests and the 512-device dry-run (Mosaic lowering needs real TPUs).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Attention (prefill/train): GQA + causal
# ---------------------------------------------------------------------------
def attention_ref(
    q: jax.Array,            # (B, S_q, H, hd)
    k: jax.Array,            # (B, S_k, K, hd)
    v: jax.Array,            # (B, S_k, K, hd)
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,       # absolute position of q[0] (cached prefix len)
    kv_len: Optional[jax.Array] = None,  # (B,) valid kv length per batch
    window: Optional[int] = None,  # keys more than window-1 back are masked
) -> jax.Array:
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    hd_v = v.shape[-1]            # MLA: v head dim may differ from q/k
    g = H // K
    scale = scale if scale is not None else hd ** -0.5
    qf = q.astype(jnp.float32) * scale
    qf = qf.reshape(B, Sq, K, g, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    mask = jnp.zeros((B, 1, 1, Sq, Sk), dtype=jnp.float32)
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    if causal:
        mask = mask + jnp.where(kpos > qpos, NEG_INF, 0.0)[None, None, None]
    if window is not None:
        mask = mask + jnp.where(kpos <= qpos - window, NEG_INF, 0.0)[None, None, None]
    if kv_len is not None:
        valid = jnp.arange(Sk)[None, :] < kv_len[:, None]
        mask = mask + jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
    w = jax.nn.softmax(logits + mask, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd_v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention: single query token vs KV cache with valid lengths
# ---------------------------------------------------------------------------
def decode_attention_ref(
    q: jax.Array,            # (B, H, hd)
    k_cache: jax.Array,      # (B, S, K, hd)
    v_cache: jax.Array,      # (B, S, K, hd)
    lengths: jax.Array,      # (B,) int32 — valid cache entries
    scale: Optional[float] = None,
    window: Optional[int] = None,  # the query (at lengths-1) sees `window` keys
) -> jax.Array:
    B, H, hd = q.shape
    _, S, K, _ = k_cache.shape
    hd_v = v_cache.shape[-1]      # MLA: v head dim may differ from q/k
    g = H // K
    scale = scale if scale is not None else hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, K, g, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
    valid = jnp.arange(S)[None, :] < lengths[:, None]          # (B, S)
    if window is not None:
        valid &= jnp.arange(S)[None, :] >= (lengths - window)[:, None]
    logits = logits + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, H, hd_v).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention: single query token vs a paged KV pool addressed
# through per-request block tables (vLLM-style PagedAttention)
# ---------------------------------------------------------------------------
def stored_pages(pages: jax.Array) -> jax.Array:
    """Pages ``(..., P, page_size, K, hd)`` -> stored ``(..., P, page_size, K·hd)``.

    A stored page is ``page_size`` rows of the K/V projection's width;
    the paged kernels and the model step read and write this layout.
    """
    return pages.reshape(*pages.shape[:-2], -1)


def page_view(pool: jax.Array, n_kv_heads: int) -> jax.Array:
    """Inverse of :func:`stored_pages`: ``(..., P, page_size, K, hd)``."""
    return pool.reshape(*pool.shape[:-1], n_kv_heads, -1)


def stored_scales(scales: jax.Array) -> jax.Array:
    """``(..., P, page_size, K)`` scales -> the stored ``(..., P, 1, page_size·K)``.

    A page's scales are one slot-major lane row.
    """
    return scales.reshape(*scales.shape[:-2], 1, -1)


def scale_view(scales: jax.Array, n_kv_heads: int) -> jax.Array:
    """Inverse of :func:`stored_scales`: ``(..., P, page_size, K)``."""
    return scales.reshape(*scales.shape[:-2], -1, n_kv_heads)


def gather_pages(
    pages: jax.Array,         # (P, page_size, K, hd) page view of one layer
    block_tables: jax.Array,  # (B, pages_per_seq) int32 page ids
) -> jax.Array:
    """Materialize the dense (B, S, K, hd) view a block table describes.

    Token t of request b lives at (block_tables[b, t // ps], t % ps);
    gathering page-by-page therefore reconstructs positions in order.
    """
    P, ps, K, hd = pages.shape
    B, npp = block_tables.shape
    flat = pages.reshape(P * ps, K, hd)
    tok = block_tables[:, :, None] * ps + jnp.arange(ps)[None, None, :]
    return flat[tok.reshape(B, npp * ps)]


def _layer_pages(pool, scales, layer, n_kv_heads):
    """One layer of a stored pool as float pages ``(P, page_size, K, hd)``."""
    pages = page_view(pool[layer], n_kv_heads)
    if scales is not None:
        pages = dequantize_pages_ref(pages, scale_view(scales[layer], n_kv_heads))
    return pages


def paged_decode_attention_ref(
    q: jax.Array,             # (B, H, hd)
    k_pages: jax.Array,       # (L, P, page_size, K·hd) stored pools
    v_pages: jax.Array,       # (L, P, page_size, K·hd_v)
    block_tables: jax.Array,  # (B, pages_per_seq) int32
    lengths: jax.Array,       # (B,) int32 — valid tokens (incl. current)
    layer=0,                  # which of the L pools
    scale: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,  # (L, P, 1, page_size·K) f32
    v_scales: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    K = k_pages.shape[-1] // q.shape[-1]
    k_dense = gather_pages(_layer_pages(k_pages, k_scales, layer, K), block_tables)
    v_dense = gather_pages(_layer_pages(v_pages, v_scales, layer, K), block_tables)
    return decode_attention_ref(q, k_dense, v_dense, lengths, scale=scale,
                                window=window)


# ---------------------------------------------------------------------------
# Paged chunked-prefill attention: C chunk queries of a single request vs
# the context pages named by its block table (history + in-chunk segment,
# both already scattered into the pool)
# ---------------------------------------------------------------------------
def paged_prefill_attention_ref(
    q: jax.Array,            # (C, H, hd) — one request's chunk queries
    k_pages: jax.Array,      # (L, P, page_size, K·hd) stored pools
    v_pages: jax.Array,      # (L, P, page_size, K·hd_v)
    block_table: jax.Array,  # (pages_per_seq,) int32
    past: int,               # prompt tokens already prefilled (chunk offset)
    layer=0,                 # which of the L pools
    scale: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,  # (L, P, 1, page_size·K) f32
    v_scales: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    K = k_pages.shape[-1] // q.shape[-1]
    C = q.shape[0]
    ps = k_pages.shape[2]
    ctx = past + C
    n_ctx_pages = -(-ctx // ps)
    bt = block_table[None, :n_ctx_pages]
    k_ctx = gather_pages(_layer_pages(k_pages, k_scales, layer, K), bt)
    v_ctx = gather_pages(_layer_pages(v_pages, v_scales, layer, K), bt)
    kv_len = jnp.array([ctx], jnp.int32)
    out = attention_ref(
        q[None], k_ctx, v_ctx, causal=True, scale=scale,
        q_offset=past, kv_len=kv_len, window=window,
    )
    return out[0]


# ---------------------------------------------------------------------------
# Held experts: a device's share of a routed expert layer
# ---------------------------------------------------------------------------
def swiglu_ref(x: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """One SwiGLU expert, float32 inside, in ``x``'s dtype out."""
    h = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(h) * u).astype(x.dtype)
    return jnp.dot(a, w_down, preferred_element_type=jnp.float32).astype(x.dtype)


def expert_ffn_ref(
    x: jax.Array,            # (M, d) rows grouped by expert, tile-aligned
    w_gate: jax.Array,       # (E_h, d, f) the held experts
    w_up: jax.Array,         # (E_h, d, f)
    w_down: jax.Array,       # (E_h, f, d)
    tile_start: jax.Array,   # (E_h,) int32 first row tile of each expert
    tile_count: jax.Array,   # (E_h,) int32 row tiles of each expert
    tile: int,               # rows per tile
) -> jax.Array:
    """Grouped SwiGLU: row tile ``t`` runs through expert ``e`` when
    ``tile_start[e] <= t < tile_start[e] + tile_count[e]``; rows of no
    expert's tiles come out 0."""
    t = jnp.arange(x.shape[0]) // tile
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        mine = (t >= tile_start[e]) & (t < tile_start[e] + tile_count[e])
        y = swiglu_ref(x, w_gate[e], w_up[e], w_down[e]).astype(jnp.float32)
        out = out + jnp.where(mine[:, None], y, 0.0)
    return out.astype(x.dtype)


def held_experts_ref(
    x: jax.Array,            # (T, d)
    ids: jax.Array,          # (T, k) routed experts, of all the router's
    weights: jax.Array,      # (T, k) float32 combine weights
    w_gate: jax.Array,       # (E_h, d, f) experts first .. first+E_h-1
    w_up: jax.Array,
    w_down: jax.Array,
    first: int = 0,
) -> jax.Array:
    """The one-hot oracle of a held-expert layer, float32 out.

    Every held expert runs on every token; a token takes the weighted
    sum over its routed experts that are held.  Assignments to other
    experts add nothing.
    """
    E_h = w_gate.shape[0]
    local = ids - first
    comb = jnp.zeros((x.shape[0], E_h + 1), jnp.float32)
    slot = jnp.where((local >= 0) & (local < E_h), local, E_h)
    comb = comb.at[jnp.arange(x.shape[0])[:, None], slot].add(weights)[:, :E_h]
    h = jnp.einsum("td,edf->tef", x, w_gate)
    u = jnp.einsum("td,edf->tef", x, w_up)
    y_e = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, w_down)
    return jnp.einsum("ted,te->td", y_e.astype(jnp.float32), comb)


# ---------------------------------------------------------------------------
# int8 KV-page quantization: symmetric per (token-slot, kv-head) scales,
# stored page-major alongside the pools ("per-page scale pools")
# ---------------------------------------------------------------------------
def quantize_kv_ref(x: jax.Array):
    """Quantize K/V values to int8 with per (…, kv-head) symmetric scales.

    ``x`` is ``(..., K, hd)``; returns ``(q int8 (..., K, hd),
    scales f32 (..., K))`` with ``scale = max(|x|, 1e-8) / 127`` over the
    head dim — the same spec as the slot cache's ``_q8_kv``.  Each token
    is quantized exactly once, at write time, from its exact value, so
    page contents are a pure function of the tokens they hold (chunk
    boundaries, prefix-cache adoption, and migration cannot change the
    bits — the differential token-equality suites rely on this).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def write_scales_ref(
    scales: jax.Array,   # (L, P, 1, page_size·K) f32 stored scale pool
    layer,
    pages: jax.Array,    # (T,) int32
    slots: jax.Array,    # (T,) int32
    values: jax.Array,   # (T, K) f32
) -> jax.Array:
    """Scatter T tokens' per-head scales into a stored scale pool."""
    T, K = values.shape
    at = jnp.stack([jnp.broadcast_to(layer, (T,)), pages,
                    jnp.zeros_like(pages), slots * K], axis=1)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1, 2),
        scatter_dims_to_operand_dims=(0, 1, 2, 3),
    )
    return jax.lax.scatter(
        scales, at.astype(jnp.int32), values.astype(scales.dtype), dims
    )


def dequantize_pages_ref(
    pages: jax.Array,    # (P, page_size, K, hd) int8 page view
    scales: jax.Array,   # (P, page_size, K) f32 scale view
) -> jax.Array:
    """Reconstruct float32 pages from an int8 pool and its scale pool."""
    return pages.astype(jnp.float32) * scales[..., None]


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm_ref(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mamba selective scan (SSM):  h_t = dA_t ⊙ h_{t-1} + dB_t x_t ;  y = C_t·h
# ---------------------------------------------------------------------------
def ssm_scan_ref(
    x: jax.Array,       # (B, T, D)  post-conv activations
    dt: jax.Array,      # (B, T, D)  softplus'd step sizes
    A: jax.Array,       # (D, N)     negative decay matrix
    Bm: jax.Array,      # (B, T, N)  input matrix
    Cm: jax.Array,      # (B, T, N)  output matrix
    D: jax.Array,       # (D,)       skip
    h0: Optional[jax.Array] = None,  # (B, D, N) initial state
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,T,D), h_T (B,D,N)). float32 state math."""
    Bsz, T, Dd = x.shape
    N = A.shape[1]
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    h = jnp.zeros((Bsz, Dd, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, Bt, Ct = inp  # (B,D), (B,D), (B,N), (B,N)
        dA = jnp.exp(dtt[..., None] * Af[None])          # (B, D, N)
        dBx = (dtt * xt)[..., None] * Bt[:, None, :]     # (B, D, N)
        h = dA * h + dBx
        y = jnp.einsum("bdn,bn->bd", h, Ct)
        return h, y

    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bf, 1, 0),
        jnp.moveaxis(Cf, 1, 0),
    )
    h, ys = jax.lax.scan(step, h, xs)
    y = jnp.moveaxis(ys, 0, 1) + xf * D.astype(jnp.float32)[None, None, :]
    return y.astype(x.dtype), h
