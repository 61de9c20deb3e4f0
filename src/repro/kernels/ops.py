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
) -> jax.Array:
    """Multi-head (GQA) attention — flash kernel on TPU, oracle elsewhere."""
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
) -> jax.Array:
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
    scale: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    impl: str = "auto",
) -> jax.Array:
    """Decode attention over a paged KV pool via per-request block tables.

    With ``k_scales``/``v_scales`` the pools are int8 and dequantized
    per page inside the kernel (oracle: dequantize-then-attend).
    """
    if _pick(impl) == "pallas":
        from .paged_attention import paged_decode_attention as _pda

        return _pda(
            q, k_pages, v_pages, block_tables, lengths, scale=scale,
            k_scales=k_scales, v_scales=v_scales,
        )
    return _ref.paged_decode_attention_ref(
        q, k_pages, v_pages, block_tables, lengths, scale=scale,
        k_scales=k_scales, v_scales=v_scales,
    )


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    past: int,
    scale: Optional[float] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    impl: str = "auto",
) -> jax.Array:
    """Fused chunked-prefill attention over one request's block table.

    The chunk's K/V must already be scattered into the pools; queries
    attend causally to paged history + the in-chunk segment.  With
    ``k_scales``/``v_scales`` the pools are int8 (see decode).
    """
    if _pick(impl) == "pallas":
        from .paged_attention import paged_prefill_attention as _ppa

        return _ppa(
            q, k_pages, v_pages, block_table, past, scale=scale,
            k_scales=k_scales, v_scales=v_scales,
        )
    return _ref.paged_prefill_attention_ref(
        q, k_pages, v_pages, block_table, past, scale=scale,
        k_scales=k_scales, v_scales=v_scales,
    )


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
