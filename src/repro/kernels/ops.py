"""Jitted dispatch layer over the Pallas kernels and their jnp oracles.

Models call these entry points; ``impl`` selects:
- "ref"      : pure-jnp oracle (CPU smoke tests, SPMD dry-run — Mosaic
               lowering requires a real TPU backend);
- "pallas"   : pl.pallas_call kernel compiled for the TPU (the kernels
               run in interpret mode only when a caller passes
               ``interpret=True`` to them directly, as the kernel tests
               do on the CPU);
- "auto"     : pallas on TPU backends, ref elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as _ref


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _pick(impl: str) -> str:
    return _default_impl() if impl == "auto" else impl


def _no_window_kernel(impl: str, name: str) -> None:
    if _pick(impl) == "pallas":
        raise NotImplementedError(
            f"ops.{name}: the Pallas kernel has no sliding window; a windowed "
            "model runs on the TPU through the paged kernels")


# ---------------------------------------------------------------------------
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-head (GQA) attention — flash kernel on TPU, oracle elsewhere.

    The flash kernel has no sliding ``window``: with one, ``pallas``
    raises and the oracle serves the CPU (the served path's windows are
    in the paged kernels).
    """
    if window is not None:
        _no_window_kernel(impl, "attention")
        return _ref.attention_ref(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            kv_len=kv_len, window=window,
        )
    if _pick(impl) == "pallas":
        from .flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset, kv_len=kv_len
        )
    return _ref.attention_ref(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset, kv_len=kv_len
    )


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    scale: Optional[float] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> jax.Array:
    """Slot-cache decode attention; a ``window`` as in :func:`attention`."""
    if window is not None:
        _no_window_kernel(impl, "decode_attention")
        return _ref.decode_attention_ref(
            q, k_cache, v_cache, lengths, scale=scale, window=window
        )
    if _pick(impl) == "pallas":
        from .decode_attention import decode_attention as _da

        return _da(q, k_cache, v_cache, lengths, scale=scale)
    return _ref.decode_attention_ref(q, k_cache, v_cache, lengths, scale=scale)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    layer=0,
    scale: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> jax.Array:
    """Decode attention over layer ``layer`` of stacked paged KV pools.

    Pools are stored ``(L, P, page_size, K·hd)`` (see
    :mod:`repro.kernels.paged_attention`).  With ``k_scales``/``v_scales``
    the pools are int8 and dequantized per page inside the kernel
    (oracle: dequantize-then-attend).  ``window`` (static) limits each
    query to the last ``window`` positions.
    """
    if _pick(impl) == "pallas":
        from .paged_attention import paged_decode_attention as _pda

        return _pda(
            q, k_pages, v_pages, block_tables, lengths, layer, scale=scale,
            k_scales=k_scales, v_scales=v_scales, window=window,
        )
    return _ref.paged_decode_attention_ref(
        q, k_pages, v_pages, block_tables, lengths, layer, scale=scale,
        k_scales=k_scales, v_scales=v_scales, window=window,
    )


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    past: int,
    layer=0,
    scale: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    impl: str = "auto",
    window: Optional[int] = None,
) -> jax.Array:
    """Fused chunked-prefill attention over one request's block table.

    The chunk's K/V must already be scattered into layer ``layer`` of
    the stacked pools; queries attend causally to paged history + the
    in-chunk segment.  With ``k_scales``/``v_scales`` the pools are int8
    (see decode); ``window`` as in decode.
    """
    if _pick(impl) == "pallas":
        from .paged_attention import paged_prefill_attention as _ppa

        return _ppa(
            q, k_pages, v_pages, block_table, past, layer, scale=scale,
            k_scales=k_scales, v_scales=v_scales, window=window,
        )
    return _ref.paged_prefill_attention_ref(
        q, k_pages, v_pages, block_table, past, layer, scale=scale,
        k_scales=k_scales, v_scales=v_scales, window=window,
    )


def expert_ffn(
    x: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    tile_start: jax.Array,
    tile_count: jax.Array,
    max_tiles: int,
    layer=0,
    impl: str = "auto",
) -> jax.Array:
    """Grouped SwiGLU over held experts; rows tile-aligned by expert.

    The weights are stacked ``(L, E_h, ...)`` and ``layer`` picks one.
    See :mod:`repro.kernels.expert_ffn`; the oracle runs every expert
    on every row and keeps each row's own.
    """
    if _pick(impl) == "pallas":
        from .expert_ffn import expert_ffn as _ef

        return _ef(x, w_gate, w_up, w_down, tile_start, tile_count, max_tiles,
                   layer)
    from .expert_ffn import ROW_TILE

    return _ref.expert_ffn_ref(
        x, w_gate[layer], w_up[layer], w_down[layer], tile_start, tile_count,
        ROW_TILE,
    )


def write_scales(
    scales: jax.Array,
    layer,
    pages: jax.Array,
    slots: jax.Array,
    values: jax.Array,
    impl: str = "auto",
) -> jax.Array:
    """Set tokens' per-head scales in a stored ``(L, P, 1, ps·K)`` scale pool.

    Token ``t``'s ``K`` scales land in slot ``slots[t]`` of page
    ``pages[t]`` of layer ``layer``; returns the updated pool.
    """
    if _pick(impl) == "pallas":
        from .paged_attention import write_scales as _ws

        return _ws(scales, layer, pages, slots, values)
    return _ref.write_scales_ref(scales, layer, pages, slots, values)


def quantize_kv(x: jax.Array):
    """Symmetric int8 KV quantization (per token, per kv head).

    Pure elementwise math — one spec shared by the paged engine's
    quantize-on-scatter and the oracles, so there is nothing to
    dispatch; see :func:`repro.kernels.ref.quantize_kv_ref`.
    """
    return _ref.quantize_kv_ref(x)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5,
            impl: str = "auto") -> jax.Array:
    if _pick(impl) == "pallas":
        from .rmsnorm import rmsnorm as _rn

        return _rn(x, gamma, eps=eps)
    return _ref.rmsnorm_ref(x, gamma, eps=eps)


def ssm_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    D: jax.Array,
    h0: Optional[jax.Array] = None,
    impl: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    if _pick(impl) == "pallas":
        from .ssm_scan import ssm_scan as _ss

        return _ss(x, dt, A, Bm, Cm, D, h0=h0)
    return _ref.ssm_scan_ref(x, dt, A, Bm, Cm, D, h0=h0)
