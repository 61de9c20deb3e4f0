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

Supported architectures are the pure-attention decoders: every layer
``attn+{mlp,dense_mlp,moe}``, with no cross/MLA/recurrent layer and no
slot-cache int8 mode — checked by :func:`supports_paged`.  Layers may
differ: each scan position passes its own static sliding window and
rotary flag (NoPE full-attention layers) to the kernels, and the
unscanned leading layers (whole pattern periods that cover the leading
dense layers) run before the scan over a pool of their own.  Every
layer keeps every page under the shared block table, windowed or not.
Held-expert layers count the tokens they route, per held expert and to
the experts held elsewhere, in ``pools["experts"]``, which the step
programs carry with the pools.  The
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
from .transformer import _post, _scan_layout, ffn_sublayer, layer_kind

Params = Dict[str, Any]
Pools = Dict[str, Any]


def supports_paged(cfg: ModelConfig) -> bool:
    """True when every layer's mixer is plain GQA self-attention.

    Layers may differ in window and rotary, leading layers may be
    unscanned (their kinds are read one by one), and the feed-forward
    may be a dense MLP or an expert layer; the slot cache's int8 mode,
    latent (MLA), recurrent, cross-attention and encoder layers are not
    paged.
    """
    if cfg.family not in ("dense", "moe") or cfg.kv_cache_dtype == "int8":
        return False
    if cfg.mla is not None or cfg.mamba is not None or cfg.encoder is not None:
        return False
    n_prefix, pat, n_sb = _scan_layout(cfg)
    if n_sb == 0:
        return False
    kinds = [layer_kind(cfg, i).split("+")[0] for i in range(n_prefix + pat)]
    return all(k == "attn" for k in kinds)


KV_DTYPES = ("fp32", "int8")


def _counts_expert(cfg: ModelConfig) -> bool:
    """Whether the step programs count routed tokens (held-expert layers)."""
    return cfg.moe is not None and cfg.moe.impl == "held"


def init_paged_pools(
    cfg: ModelConfig, num_pages: int, page_size: int, kv_dtype: str = "fp32"
) -> Pools:
    """Per-pattern-position page pools, stacked over superblocks.

    ``pools["blocks"][j]`` is ``{"k","v": (n_sb, P, page_size, K·hd)}``:
    the page axis is axis 1, and a page is ``page_size`` rows of the
    K/V projection's own width (head ``h`` of slot ``t`` in columns
    ``h·hd .. (h+1)·hd`` of row ``t``), the layout the paged kernels
    read in place and whose default TPU layout is row-major.  The
    unscanned leading layers share one more pool of the same form,
    ``pools["blocks"]["prefix"]``, stacked over those layers.  With
    ``kv_dtype="int8"`` the K/V leaves are int8 and per-page scale pools
    ride alongside: ``{"k_s","v_s": (n_sb, P, 1, page_size·K) float32}``,
    one scale per token slot per kv head, a page's ``(page_size, K)``
    scales flattened slot-major into one row; initialised to a neutral
    scale of 1 (never-written slots dequantize to finite values the
    kernels' masking then discards).

    A model with held experts also gets ``pools["experts"]``: int32
    ``(held + 1,)``, the tokens the step programs have routed to each
    held expert and, last, to experts held elsewhere, summed over
    layers (decode's padding rows excluded).
    """
    if not supports_paged(cfg):
        raise ValueError(
            f"config {cfg.name!r} is not paged-KV compatible "
            "(requires a pure-attention decoder, fp/bf16 cache)"
        )
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
    n_prefix, pat, n_sb = _scan_layout(cfg)
    K, hd = cfg.n_kv_heads, cfg.hd

    def one_pool(n):
        kv_shape = (n, num_pages, page_size, K * hd)
        scale_shape = (n, num_pages, 1, page_size * K)
        if kv_dtype == "int8":
            return {
                "k": jnp.zeros(kv_shape, jnp.int8),
                "v": jnp.zeros(kv_shape, jnp.int8),
                "k_s": jnp.ones(scale_shape, jnp.float32),
                "v_s": jnp.ones(scale_shape, jnp.float32),
            }
        return {"k": jnp.zeros(kv_shape, cfg.jdtype),
                "v": jnp.zeros(kv_shape, cfg.jdtype)}

    blocks = {str(j): one_pool(n_sb) for j in range(pat)}
    if n_prefix:
        blocks["prefix"] = one_pool(n_prefix)
    pools: Pools = {"blocks": blocks}
    if _counts_expert(cfg):
        pools["experts"] = jnp.zeros((cfg.moe.held + 1,), jnp.int32)
    return pools


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
    """Run every layer with the stacked pools in the loop's carry.

    ``layer(p, pool, i, li, x) -> (x, pool, counts)`` runs absolute
    layer ``li`` (static: its kind, window and rotary) on layer ``i`` of
    ``pool`` (a traced superblock index, or the static index of an
    unscanned leading layer in the prefix pool); ``counts`` is its
    routed-token count or None.  Carrying the pools, rather than
    scanning them as per-layer slices, lets each layer write its tokens
    in place and the kernels read by layer index.  Held experts' weights
    stay out of the scan too: ``p["moe"]`` gets them stacked over the
    superblocks, for the grouped kernel to read layer ``i`` in place (a
    scanned slice would be copied out for the kernel, every layer).
    """
    n_prefix, pat, n_sb = _scan_layout(cfg)
    scanned, experts = dict(params["blocks"]), {}
    if _counts_expert(cfg):
        names = ("w_gate", "w_up", "w_down")
        for j, p in scanned.items():
            moe = dict(p["moe"])
            experts[j] = {n: moe.pop(n) for n in names}
            scanned[j] = {**p, "moe": moe}
    blocks = dict(pools["blocks"])
    counts = jnp.zeros_like(pools["experts"]) if "experts" in pools else None
    for i in range(n_prefix):
        x, blocks["prefix"], c = layer(params[f"prefix_{i}"], blocks["prefix"], i, i, x)
        if c is not None:
            counts = counts + c
    prefix = blocks.pop("prefix", None)

    def body(carry, xs):
        x, blocks, counts = carry
        p, i = xs
        blocks = dict(blocks)
        for j in range(pat):
            pj = p[str(j)]
            if experts:
                pj = {**pj, "moe": {**pj["moe"], **experts[str(j)]}}
            x, blocks[str(j)], c = layer(pj, blocks[str(j)], i, n_prefix + j, x)
            if c is not None:
                counts = counts + c
        return (x, blocks, counts), None

    (x, blocks, counts), _ = jax.lax.scan(
        body, (x, blocks, counts), (scanned, jnp.arange(n_sb))
    )
    if prefix is not None:
        blocks["prefix"] = prefix
    out: Pools = {"blocks": blocks}
    if counts is not None:
        out["experts"] = pools["experts"] + counts
    return x, out


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
    """One decode step over paged KV; returns (logits (B, V), pools).

    Rows of length 0 are padding: they write the trash page, and the
    routed-token count leaves them out.
    """
    B = tokens.shape[0]
    ps = pools["blocks"]["0"]["k"].shape[2]
    x = L.embed_tokens(params, cfg, tokens[:, None])

    rows = jnp.arange(B)
    write_page = block_tables[rows, lengths // ps]          # (B,)
    write_slot = lengths % ps                               # (B,)
    valid = (lengths > 0)[:, None] if _counts_expert(cfg) else None

    def layer(p: Params, pool: Dict[str, jax.Array], i, li: int, x: jax.Array):
        h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
        q, k, v = L.project_qkv(p["attn"], cfg, h)          # (B,1,·,hd)
        if cfg.layer_rope(li):
            pos = lengths[:, None]
            q = L.rope(q, pos, cfg.rope_theta)
            k = L.rope(k, pos, cfg.rope_theta)
        pool = _write_kv(pool, i, write_page, write_slot, k[:, 0], v[:, 0])
        out = ops.paged_decode_attention(
            q[:, 0], pool["k"], pool["v"], block_tables, lengths + 1, i,
            k_scales=pool.get("k_s"), v_scales=pool.get("v_s"),
            window=cfg.layer_window(li),
        )
        out = L.attention_output(
            p["attn"], cfg, h, out.reshape(B, 1, cfg.n_heads * cfg.hd))
        x = x + _post(p, cfg, "post_norm1", out)
        x, counts = ffn_sublayer(p, cfg, layer_kind(cfg, li), x, decoding=True,
                                 valid=valid, layer=i)
        return x, pool, counts

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
    ps = pools["blocks"]["0"]["k"].shape[2]
    C = tokens.shape[1]
    x = L.embed_tokens(params, cfg, tokens)
    positions = (past + jnp.arange(C))[None, :]             # (1, C)
    write_page = block_table[(past + jnp.arange(C)) // ps]  # (C,)
    write_slot = (past + jnp.arange(C)) % ps

    def layer(p: Params, pool: Dict[str, jax.Array], i, li: int, x: jax.Array):
        h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
        q, k, v = L.project_qkv(
            p["attn"], cfg, h, positions if cfg.layer_rope(li) else None)
        pool = _write_kv(pool, i, write_page, write_slot, k[0], v[0])
        out = ops.paged_prefill_attention(
            q[0], pool["k"], pool["v"], block_table, past, i,
            k_scales=pool.get("k_s"), v_scales=pool.get("v_s"),
            window=cfg.layer_window(li),
        )[None]
        out = L.attention_output(
            p["attn"], cfg, h, out.reshape(1, C, cfg.n_heads * cfg.hd))
        x = x + _post(p, cfg, "post_norm1", out)
        x, counts = ffn_sublayer(p, cfg, layer_kind(cfg, li), x, layer=i)
        return x, pool, counts

    x, pools = _layer_loop(params, cfg, pools, x, layer)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params, x, cfg.tie_embeddings)
    return logits, pools
