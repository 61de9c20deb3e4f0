"""Paged KV-cache model path (vLLM-style) for the serving engine.

Instead of one dense ``(B, S_max, K, hd)`` slot cache per attention layer,
K/V live in a shared pool of fixed-size pages, and each request owns a
*block table* mapping logical token positions to physical pages.  The
same block table is shared by every layer (each layer has its own
physical pool, like vLLM), so allocation is a single host-side decision
per page.

A step program holds the pools where they are: the stacked pools ride
the layer loop's carry, each layer scatters only its new tokens' K/V
slots into them in place, and the kernels read pages straight from the
stacked pools by (layer, page) — no layer's pool is sliced out, copied
or relaid, and the donated input buffers are the outputs.

Three entry points, mirroring ``transformer.py``'s cache contract:

- :func:`init_paged_pools` — allocate the per-layer page pools;
- :func:`paged_prefill_chunk` — run one prompt chunk (attending to the
  pages written by earlier chunks) and scatter its K/V into the pools;
  chunked prefill is what lets long prompts interleave with decode steps;
  attention goes through the fused paged-prefill kernel (block tables
  via scalar prefetch) — the chunk never materializes a dense context;
- :func:`paged_decode_step` — one decode token for a batch of requests,
  writing through block tables and attending via the paged kernel.

Pools come in two flavours selected by ``kv_dtype``: ``"fp32"`` stores
pages in the model's compute dtype (the historical layout, bit-for-bit
identical to the slot path), and ``"int8"`` stores int8 pages plus
per-page scale pools (``k_s``/``v_s``, one float32 scale per token slot
per kv head) that both kernels dequantize on the fly.  Quantization
happens exactly once per token, at scatter time, from the exact value —
page bits are therefore a pure function of the tokens they hold, which
keeps prefix-cache adoption, copy-on-write, and migration
token-deterministic under int8.

Supported architectures are the pure-attention decoder families (every
layer ``attn+{mlp,dense_mlp,moe}``, no prefix/cross/MLA/recurrent
layers and no int8 cache) — checked by :func:`supports_paged`.  The
numerics intentionally match the slot path bit-for-bit under greedy
decoding: positions past a request's length are masked to an exact
softmax weight of 0 in both paths, so recycled page garbage can never
reach the output (tested token-for-token in ``tests/test_paged_engine``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from . import layers as L
from .config import ModelConfig
from .transformer import _apply_ffn, _scan_layout, layer_kind

Params = Dict[str, Any]
Pools = Dict[str, Any]


def supports_paged(cfg: ModelConfig) -> bool:
    """True when every layer's mixer is plain GQA attention."""
    if cfg.family not in ("dense", "moe") or cfg.kv_cache_dtype == "int8":
        return False
    if cfg.mla is not None or cfg.mamba is not None or cfg.encoder is not None:
        return False
    n_prefix, pat, n_sb = _scan_layout(cfg)
    if n_prefix or n_sb == 0:
        return False
    kinds = [layer_kind(cfg, j).split("+")[0] for j in range(pat)]
    return all(k == "attn" for k in kinds)


KV_DTYPES = ("fp32", "int8")


def init_paged_pools(
    cfg: ModelConfig, num_pages: int, page_size: int, kv_dtype: str = "fp32"
) -> Pools:
    """Per-pattern-position page pools, stacked over superblocks.

    ``pools["blocks"][j]`` is ``{"k","v": (n_sb, P, page_size, K·hd)}``:
    the page axis is axis 1, and a page is ``page_size`` rows of the
    K/V projection's own width (head ``h`` of slot ``t`` in columns
    ``h·hd .. (h+1)·hd`` of row ``t``), the layout the paged kernels
    read in place and whose default TPU layout is row-major.  With
    ``kv_dtype="int8"`` the K/V leaves are int8 and per-page scale pools
    ride alongside: ``{"k_s","v_s": (n_sb, P, 1, page_size·K) float32}``,
    one scale per token slot per kv head, a page's ``(page_size, K)``
    scales flattened slot-major into one row; initialised to a neutral
    scale of 1 (never-written slots dequantize to finite values the
    kernels' masking then discards).
    """
    if not supports_paged(cfg):
        raise ValueError(
            f"config {cfg.name!r} is not paged-KV compatible "
            "(requires a pure-attention decoder, fp/bf16 cache)"
        )
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    _, pat, n_sb = _scan_layout(cfg)
    K, hd = cfg.n_kv_heads, cfg.hd
    kv_shape = (n_sb, num_pages, page_size, K * hd)
    scale_shape = (n_sb, num_pages, 1, page_size * K)

    def one_pool():
        if kv_dtype == "int8":
            return {
                "k": jnp.zeros(kv_shape, jnp.int8),
                "v": jnp.zeros(kv_shape, jnp.int8),
                "k_s": jnp.ones(scale_shape, jnp.float32),
                "v_s": jnp.ones(scale_shape, jnp.float32),
            }
        return {"k": jnp.zeros(kv_shape, cfg.jdtype),
                "v": jnp.zeros(kv_shape, cfg.jdtype)}

    return {"blocks": {str(j): one_pool() for j in range(pat)}}


def _write_kv(pool: Dict[str, jax.Array], layer, page, slot, k, v):
    """Scatter one batch of K/V tokens into ``layer`` of the stacked pools.

    ``k``/``v`` are ``(T, K, hd)``; token ``i`` lands in slot
    ``slot[i]`` of page ``page[i]``, quantized when the pool is int8.
    Only those slots are written; returns the updated pool dict.
    """
    T = k.shape[0]
    out = dict(pool)
    if "k_s" in pool:
        k, ks = ops.quantize_kv(k)
        v, vs = ops.quantize_kv(v)
        out["k_s"] = ops.write_scales(pool["k_s"], layer, page, slot, ks)
        out["v_s"] = ops.write_scales(pool["v_s"], layer, page, slot, vs)
    for name, x in (("k", k), ("v", v)):
        arr = pool[name]
        out[name] = arr.at[layer, page, slot].set(
            x.reshape(T, -1).astype(arr.dtype)
        )
    return out


def _layer_loop(params: Params, cfg: ModelConfig, pools: Pools, x, layer):
    """Run every superblock with the stacked pools in the loop's carry.

    ``layer(p, pool, i, j, x) -> (x, pool)`` runs pattern position
    ``j`` of superblock ``i`` (a traced index).  Carrying the pools,
    rather than scanning them as per-layer slices, lets each layer write
    its tokens in place and the kernels read by layer index.
    """
    _, pat, n_sb = _scan_layout(cfg)

    def body(carry, xs):
        x, blocks = carry
        p, i = xs
        blocks = dict(blocks)
        for j in range(pat):
            x, blocks[str(j)] = layer(p[str(j)], blocks[str(j)], i, j, x)
        return (x, blocks), None

    (x, blocks), _ = jax.lax.scan(
        body, (x, pools["blocks"]), (params["blocks"], jnp.arange(n_sb))
    )
    return x, {"blocks": blocks}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def paged_decode_step(
    params: Params,
    cfg: ModelConfig,
    pools: Pools,
    tokens: jax.Array,        # (B,) int32 — one new token per request
    block_tables: jax.Array,  # (B, pages_per_seq) int32
    lengths: jax.Array,       # (B,) int32 — tokens already in cache
) -> Tuple[jax.Array, Pools]:
    """One decode step over paged KV; returns (logits (B, V), pools)."""
    B = tokens.shape[0]
    _, pat, _ = _scan_layout(cfg)
    ps = pools["blocks"]["0"]["k"].shape[2]
    x = L.embed(params, tokens[:, None]).astype(cfg.jdtype)

    rows = jnp.arange(B)
    write_page = block_tables[rows, lengths // ps]          # (B,)
    write_slot = lengths % ps                               # (B,)
    kinds = [layer_kind(cfg, j) for j in range(pat)]

    def layer(p: Params, pool: Dict[str, jax.Array], i, j: int, x: jax.Array):
        h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
        q, k, v = L._proj_qkv(p["attn"], cfg, h, h)         # (B,1,·,hd)
        pos = lengths[:, None]
        q = L.rope(q, pos, cfg.rope_theta)
        k = L.rope(k, pos, cfg.rope_theta)
        pool = _write_kv(pool, i, write_page, write_slot, k[:, 0], v[:, 0])
        out = ops.paged_decode_attention(
            q[:, 0], pool["k"], pool["v"], block_tables, lengths + 1, i,
            k_scales=pool.get("k_s"), v_scales=pool.get("v_s"),
        )
        x = x + out.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["attn"]["wo"]
        x = _apply_ffn(p, cfg, kinds[j], x, decoding=True)
        return x, pool

    x, pools = _layer_loop(params, cfg, pools, x, layer)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params, x, cfg.tie_embeddings)
    return logits[:, 0], pools


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------
def paged_prefill_chunk(
    params: Params,
    cfg: ModelConfig,
    pools: Pools,
    tokens: jax.Array,        # (1, C) int32 — this chunk of the prompt
    block_table: jax.Array,   # (pages_per_seq,) int32
    past: int,                # tokens of this prompt already prefilled
) -> Tuple[jax.Array, Pools]:
    """Run one prompt chunk for a single request; returns (logits, pools).

    The chunk's queries attend causally to (already-paged history + the
    chunk itself); its K/V are scattered into the pools at positions
    ``past .. past+C`` and attention runs through the fused paged-prefill
    kernel over the block table — no dense context view is gathered.
    ``past`` is static per jit specialization — chunk boundaries are
    multiples of the chunk size, so the number of distinct compilations
    is tiny.  Returned logits cover the whole chunk, ``(1, C, V)``.
    """
    _, pat, _ = _scan_layout(cfg)
    ps = pools["blocks"]["0"]["k"].shape[2]
    C = tokens.shape[1]
    x = L.embed(params, tokens).astype(cfg.jdtype)
    positions = (past + jnp.arange(C))[None, :]             # (1, C)
    write_page = block_table[(past + jnp.arange(C)) // ps]  # (C,)
    write_slot = (past + jnp.arange(C)) % ps
    kinds = [layer_kind(cfg, j) for j in range(pat)]

    def layer(p: Params, pool: Dict[str, jax.Array], i, j: int, x: jax.Array):
        h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
        q, k, v = L._proj_qkv(p["attn"], cfg, h, h)
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
        pool = _write_kv(pool, i, write_page, write_slot, k[0], v[0])
        out = ops.paged_prefill_attention(
            q[0], pool["k"], pool["v"], block_table, past, i,
            k_scales=pool.get("k_s"), v_scales=pool.get("v_s"),
        )[None]
        x = x + out.reshape(1, C, cfg.n_heads * cfg.hd) @ p["attn"]["wo"]
        x = _apply_ffn(p, cfg, kinds[j], x)
        return x, pool

    x, pools = _layer_loop(params, cfg, pools, x, layer)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params, x, cfg.tie_embeddings)
    return logits, pools
