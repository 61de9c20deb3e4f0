"""Core transformer layers: functional JAX (no flax), params as pytrees.

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the
param tree with tuples of *logical* axis names (resolved to PartitionSpecs
by :mod:`repro.distributed.sharding`).  Weights follow the 2D production
sharding: tensor dims on ``model``, fsdp dim (``embed``) on ``data``.

Attention / norm / scan hot-spots call :mod:`repro.kernels.ops`, which
dispatches to Pallas kernels on TPU and their jnp oracles elsewhere.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .config import ModelConfig

Params = Dict[str, Any]
Specs = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def _dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class Maker:
    """Param factory: builds matching (params, specs) trees."""

    def __init__(self, key: jax.Array, dtype) -> None:
        self.key = key
        self.dtype = dtype
        self.params: Params = {}
        self.specs: Specs = {}
        self._n = 0

    def sub(self, name: str) -> "Maker":
        # crc32, not hash(): str hashes are salted per process, and the
        # same seed must give the same weights in every process
        salt = zlib.crc32(name.encode()) % (2 ** 31)
        m = Maker(jax.random.fold_in(self.key, salt), self.dtype)
        self.params[name] = m.params
        self.specs[name] = m.specs
        return m

    def dense(self, name: str, shape, spec, fan_in=None, zeros=False, ones=False):
        self._n += 1
        k = jax.random.fold_in(self.key, self._n)
        if ones:
            arr = jnp.ones(shape, self.dtype)
        elif zeros:
            arr = jnp.zeros(shape, self.dtype)
        else:
            arr = _dense_init(k, shape, self.dtype, fan_in)
        self.params[name] = arr
        self.specs[name] = tuple(spec)
        return arr

    def f32(self, name: str, value: jax.Array, spec):
        self.params[name] = value.astype(jnp.float32)
        self.specs[name] = tuple(spec)
        return value


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def init_rmsnorm(mk: Maker, name: str, d: int) -> None:
    mk.dense(name, (d,), (None,), ones=True)


def rmsnorm(gamma: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return ops.rmsnorm(x, gamma, eps=eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32.

    Rotates each head's halves, ``(x1, x2) -> (x1·cos - x2·sin,
    x2·cos + x1·sin)``, on the flat ``(B, S, H·hd)`` view: on a TPU the
    per-head form makes the compiler emit the q/k projections head-major
    and copy each layer's projection weights into that layout first.
    """
    B, S, H, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
    sin = jnp.sin(ang)
    cos = jnp.tile(jnp.cos(ang), (1, 1, 2 * H))            # (B,S,H·hd)
    sin = jnp.tile(jnp.concatenate([-sin, sin], axis=-1), (1, 1, H))
    xf = x.reshape(B, S, H * hd)
    first = jnp.arange(H * hd) % hd < half
    # each lane's partner in its own head: x2 for the first half, x1 after
    partner = jnp.where(first, jnp.roll(xf, -half, axis=-1),
                        jnp.roll(xf, half, axis=-1))
    return (xf * cos + partner * sin).astype(x.dtype).reshape(x.shape)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def init_attention(mk: Maker, cfg: ModelConfig, cross: bool = False) -> None:
    d, hd = cfg.d_model, cfg.hd
    h, k = cfg.n_heads, cfg.n_kv_heads
    mk.dense("wq", (d, h * hd), ("embed", "heads"))
    mk.dense("wk", (d, k * hd), ("embed", "kv_heads"))
    mk.dense("wv", (d, k * hd), ("embed", "kv_heads"))
    mk.dense("wo", (h * hd, d), ("heads", "embed"))
    if cfg.qkv_bias and not cross:
        mk.dense("bq", (h * hd,), ("heads",), zeros=True)
        mk.dense("bk", (k * hd,), ("kv_heads",), zeros=True)
        mk.dense("bv", (k * hd,), ("kv_heads",), zeros=True)


def _proj_qkv(p: Params, cfg: ModelConfig, x: jax.Array, kv_src: jax.Array):
    B, S, _ = x.shape
    Skv = kv_src.shape[1]
    h, k, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, h, hd)
    kk = (kv_src @ p["wk"]).reshape(B, Skv, k, hd)
    v = (kv_src @ p["wv"]).reshape(B, Skv, k, hd)
    if "bq" in p:
        q = q + p["bq"].reshape(1, 1, h, hd)
        kk = kk + p["bk"].reshape(1, 1, k, hd)
        v = v + p["bv"].reshape(1, 1, k, hd)
    return q, kk, v


def attention_full(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                  # (B, S, d)
    positions: jax.Array,          # (B, S)
    causal: bool = True,
    use_rope: bool = True,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence attention (train / prefill).  Returns (out, kv)."""
    q, k, v = _proj_qkv(p, cfg, x, x)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    out = ops.attention(q, k, v, causal=causal)
    B, S, _ = x.shape
    out = out.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, {"k": k, "v": v}


def attention_decode(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                  # (B, 1, d)
    cache_k: jax.Array,            # (B, S_max, K, hd)
    cache_v: jax.Array,
    lengths: jax.Array,            # (B,) tokens already in cache
    use_rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode; returns (out, new_cache_k, new_cache_v)."""
    B = x.shape[0]
    q, k, v = _proj_qkv(p, cfg, x, x)
    if use_rope:
        pos = lengths[:, None]
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    # write new kv at position `lengths`
    onehot = jax.nn.one_hot(lengths, cache_k.shape[1], dtype=cache_k.dtype)
    cache_k = cache_k + onehot[:, :, None, None] * k.astype(cache_k.dtype)
    cache_v = cache_v + onehot[:, :, None, None] * v.astype(cache_v.dtype)
    out = ops.decode_attention(
        q[:, 0], cache_k, cache_v, lengths + 1
    )
    out = out.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, cache_k, cache_v


def _q8_kv(x: jax.Array):
    """Symmetric int8 per (batch, pos, head): scale over head_dim."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dq8_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def attention_decode_q8(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                  # (B, 1, d)
    cache: Dict[str, jax.Array],   # {k,v: int8 (B,S,K,hd); k_s,v_s: (B,S,K)}
    lengths: jax.Array,
    use_rope: bool = True,
):
    """Decode over an int8-quantized KV cache (serving memory optimization).

    New K/V are quantized at write; the cached payload is dequantized on
    the fly inside the attention contraction (XLA fuses convert×dot, so no
    bf16 copy of the cache materializes on TPU).
    """
    B = x.shape[0]
    q, k, v = _proj_qkv(p, cfg, x, x)
    if use_rope:
        pos = lengths[:, None]
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    kq, ks = _q8_kv(k)     # (B,1,K,hd), (B,1,K)
    vq, vs = _q8_kv(v)
    onehot = jax.nn.one_hot(lengths, cache["k"].shape[1], dtype=jnp.int8)
    sel = onehot[:, :, None, None]
    new = dict(cache)
    new["k"] = cache["k"] * (1 - sel) + sel * kq
    new["v"] = cache["v"] * (1 - sel) + sel * vq
    oh_f = onehot.astype(jnp.float32)[:, :, None]
    new["k_s"] = cache["k_s"] * (1 - oh_f) + oh_f * ks
    new["v_s"] = cache["v_s"] * (1 - oh_f) + oh_f * vs
    k_deq = _dq8_kv(new["k"], new["k_s"], cfg.jdtype)
    v_deq = _dq8_kv(new["v"], new["v_s"], cfg.jdtype)
    out = ops.decode_attention(q[:, 0], k_deq, v_deq, lengths + 1)
    out = out.reshape(B, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, new


def cross_attention(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,                   # (B, S, d)
    enc_kv: Tuple[jax.Array, jax.Array],  # precomputed (k, v): (B, S_enc, K, hd)
) -> jax.Array:
    B, S, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, h, hd)
    k, v = enc_kv
    out = ops.attention(q, k, v, causal=False)
    return out.reshape(B, S, h * hd) @ p["wo"]


def cross_kv(p: Params, cfg: ModelConfig, enc_out: jax.Array):
    B, Se, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, Se, cfg.n_kv_heads, cfg.hd)
    v = (enc_out @ p["wv"]).reshape(B, Se, cfg.n_kv_heads, cfg.hd)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------
def init_mla(mk: Maker, cfg: ModelConfig) -> None:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    mk.dense("wq", (d, h * qd), ("embed", "heads"))
    mk.dense("w_dkv", (d, m.kv_lora_rank + m.rope_head_dim), ("embed", "lora"))
    mk.dense("w_uk", (m.kv_lora_rank, h * m.nope_head_dim), ("lora", "heads"))
    mk.dense("w_uv", (m.kv_lora_rank, h * m.v_head_dim), ("lora", "heads"))
    mk.dense("wo", (h * m.v_head_dim, d), ("heads", "embed"))


def _mla_qkv(p, cfg, x, positions):
    """Project to MLA q / compressed kv; returns q, (c_kv, k_rope)."""
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.n_heads
    qd = m.nope_head_dim + m.rope_head_dim
    q = (x @ p["wq"]).reshape(B, S, h, qd)
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    dkv = x @ p["w_dkv"]
    c_kv, k_rope = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank:]
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand(p, cfg, c_kv, k_rope):
    """Expand compressed cache into per-head K/V (B, S, H, ·)."""
    m = cfg.mla
    B, S, _ = c_kv.shape
    h = cfg.n_heads
    k_nope = (c_kv @ p["w_uk"]).reshape(B, S, h, m.nope_head_dim)
    v = (c_kv @ p["w_uv"]).reshape(B, S, h, m.v_head_dim)
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, h, m.rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    return k, v


def mla_full(p, cfg, x, positions) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    m = cfg.mla
    B, S, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    k, v = _mla_expand(p, cfg, c_kv, k_rope)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    out = ops.attention(q, k, v, causal=True, scale=scale)
    out = out.reshape(B, S, cfg.n_heads * m.v_head_dim) @ p["wo"]
    # cache is the COMPRESSED latent (the paper's 10×+ KV saving)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_decode(p, cfg, x, cache_c, cache_r, lengths):
    m = cfg.mla
    B = x.shape[0]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, lengths[:, None])
    onehot = jax.nn.one_hot(lengths, cache_c.shape[1], dtype=cache_c.dtype)
    cache_c = cache_c + onehot[:, :, None] * c_kv.astype(cache_c.dtype)
    cache_r = cache_r + onehot[:, :, None] * k_rope.astype(cache_r.dtype)
    k, v = _mla_expand(p, cfg, cache_c, cache_r)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)[:, 0]
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    out = ops.decode_attention(q, k, v, lengths + 1, scale=scale)
    out = out.reshape(B, 1, cfg.n_heads * m.v_head_dim) @ p["wo"]
    return out, cache_c, cache_r


# ---------------------------------------------------------------------------
# Gated MLP
# ---------------------------------------------------------------------------
def init_mlp(mk: Maker, d: int, ff: int) -> None:
    mk.dense("w_gate", (d, ff), ("embed", "ff"))
    mk.dense("w_up", (d, ff), ("embed", "ff"))
    mk.dense("w_down", (ff, d), ("ff", "embed"))


def mlp(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def mlp_ws_decode(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Weight-stationary decode MLP (serve_opt2 variant).

    At decode the activations are tiny (B tokens) while the fsdp-sharded
    weights are huge; XLA's SPMD partitioner still all-gathers the weight
    shards every step.  This shard_map keeps every weight shard where it
    lives and moves only activation partials:

        x (replicated) --slice d over data--> partial h  --psum(data)-->
        silu·u --local (ff/model)--> partial y --psum(model)--> y(d/data)

    Collective bytes per layer drop from O(|W|/model) to O(B·d) —
    ~40x for llama3-405b decode_32k.
    """
    from jax.sharding import PartitionSpec as P

    from ..distributed.sharding import active_mesh

    mesh = active_mesh()
    d = cfg.d_model
    ff = p["w_gate"].shape[-1]
    if (
        mesh is None
        or "data" not in mesh.shape
        or "model" not in mesh.shape
        or d % (mesh.shape["data"]) or ff % mesh.shape["model"]
        or d % mesh.shape["data"]
    ):
        return mlp(p, x)
    dsz = mesh.shape["data"]
    d_l = d // dsz

    def body(xl, wg, wu, wd):
        i = jax.lax.axis_index("data")
        xs = jax.lax.dynamic_slice_in_dim(xl, i * d_l, d_l, axis=-1)
        h = jax.lax.psum(xs @ wg, "data")          # (B,1,ff_m) bf16
        u = jax.lax.psum(xs @ wu, "data")
        a = (jax.nn.silu(h.astype(jnp.float32)) * u.astype(jnp.float32)).astype(x.dtype)
        y = jax.lax.psum(a @ wd, "model")          # (B,1,d_l)
        return y

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P("data", "model"), P("data", "model"), P("model", "data")),
        out_specs=P(None, None, "data"),
        check_vma=False,
    )(x, p["w_gate"], p["w_up"], p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------
def init_embed(mk: Maker, cfg: ModelConfig) -> None:
    mk.dense("embedding", (cfg.vocab, cfg.d_model), ("vocab", "embed"),
             fan_in=cfg.d_model)
    if not cfg.tie_embeddings:
        mk.dense("lm_head", (cfg.d_model, cfg.vocab), ("embed", "vocab"))


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed(p: Params, x: jax.Array, tie: bool) -> jax.Array:
    w = p["embedding"].T if tie else p["lm_head"]
    return x @ w
