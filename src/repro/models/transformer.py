"""Composable decoder / enc-dec transformer covering all 10 architectures.

Layer heterogeneity (Jamba's 1:7 attn:mamba, vision cross-attn every 5th,
MoE every k-th, xLSTM's sLSTM every 8th) is handled by *superblocks*: the
repeating pattern unit.  Parameters are stacked over superblocks and the
stack is traversed with ``jax.lax.scan`` — HLO size is O(pattern), not
O(depth), which keeps 512-device SPMD compiles of 126-layer models cheap
and matches production practice (MaxText).

Caches:
- attention layers: slot-based KV cache (B, S_max, K, hd) + lengths (B,)
  — TPU-idiomatic static shapes instead of paged indirection;
- MLA layers: *compressed* latent cache (B, S_max, lora+rope) with the
  weight-absorption decode path (cache never expands to per-head K/V);
- Mamba/xLSTM layers: O(1) recurrent state.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from ..kernels import ops
from .config import ModelConfig
from . import layers as L
from . import moe as M
from . import ssm as S

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Layer kinds
# ---------------------------------------------------------------------------
def layer_kind(cfg: ModelConfig, i: int) -> str:
    """What lives at absolute layer index i."""
    if cfg.family == "ssm":
        x = cfg.xlstm
        cell = "slstm" if (i % x.slstm_every) == (x.slstm_every - 1) else "mlstm"
        return f"{cell}+none"
    mixer = "attn"
    if cfg.mla is not None:
        mixer = "mla"
    if cfg.mamba is not None and not cfg.is_attn_layer(i):
        mixer = "mamba"
    if cfg.is_cross_layer(i):
        mixer = "cross"
    ffn = "mlp"
    if cfg.is_moe_layer(i):
        ffn = "moe"
    elif cfg.moe is not None and i < cfg.moe.first_dense:
        ffn = "dense_mlp"
    elif cfg.d_ff == 0:
        ffn = "none"
    return f"{mixer}+{ffn}"


def superblock_len(cfg: ModelConfig) -> int:
    periods = [1]
    if cfg.family == "ssm":
        periods.append(cfg.xlstm.slstm_every)
    if cfg.attn_period > 1:
        periods.append(cfg.attn_period)
    if cfg.window > 0 and cfg.full_attn_every > 1:
        periods.append(cfg.full_attn_every)
    if cfg.cross_attn_period > 0:
        periods.append(cfg.cross_attn_period)
    if cfg.moe is not None and cfg.moe.layer_period > 1:
        periods.append(cfg.moe.layer_period)
    return int(math.lcm(*periods))


def _scan_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_prefix, pattern_len, n_superblocks): prefix layers are unscanned.

    The prefix is the fewest whole pattern periods that cover the leading
    dense layers (DeepSeek's one layer; Trinity's two, in a first period
    of four), so every scan position keeps one layer kind, window and
    rotary."""
    pat = superblock_len(cfg)
    first_dense = cfg.moe.first_dense if cfg.moe is not None else 0
    n_prefix = -(-first_dense // pat) * pat
    rest = cfg.n_layers - n_prefix
    if rest % pat:
        # pattern does not tile the remaining depth: unscanned prefix only
        return cfg.n_layers, 1, 0
    return n_prefix, pat, rest // pat


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_layer(mk: L.Maker, cfg: ModelConfig, kind: str) -> None:
    mixer, ffn = kind.split("+")
    L.init_rmsnorm(mk, "norm1", cfg.d_model)
    if mixer == "attn":
        L.init_attention(mk.sub("attn"), cfg)
    elif mixer == "mla":
        L.init_mla(mk.sub("attn"), cfg)
    elif mixer == "cross":
        L.init_attention(mk.sub("attn"), cfg, cross=True)
    elif mixer == "mamba":
        S.init_mamba(mk.sub("mamba"), cfg)
    elif mixer == "mlstm":
        S.init_mlstm(mk.sub("cell"), cfg)
    elif mixer == "slstm":
        S.init_slstm(mk.sub("cell"), cfg)
    if ffn != "none":
        L.init_rmsnorm(mk, "norm2", cfg.d_model)
    if ffn == "mlp":
        L.init_mlp(mk.sub("mlp"), cfg.d_model, cfg.d_ff)
    elif ffn == "dense_mlp":
        L.init_mlp(mk.sub("mlp"), cfg.d_model, cfg.moe.d_ff_dense or cfg.d_ff)
    elif ffn == "moe":
        M.init_moe(mk.sub("moe"), cfg)
    if cfg.sandwich_norm:
        L.init_rmsnorm(mk, "post_norm1", cfg.d_model)
        if ffn != "none":
            L.init_rmsnorm(mk, "post_norm2", cfg.d_model)


def _init_decoder_layer_for_audio(mk: L.Maker, cfg: ModelConfig) -> None:
    """Whisper decoder layer: self-attn + cross-attn + mlp."""
    L.init_rmsnorm(mk, "norm1", cfg.d_model)
    L.init_attention(mk.sub("attn"), cfg)
    L.init_rmsnorm(mk, "norm_x", cfg.d_model)
    L.init_attention(mk.sub("xattn"), cfg, cross=True)
    L.init_rmsnorm(mk, "norm2", cfg.d_model)
    L.init_mlp(mk.sub("mlp"), cfg.d_model, cfg.d_ff)


def init_params(cfg: ModelConfig, key: jax.Array) -> Tuple[Params, Params]:
    """Returns (params, specs) with per-superblock stacked layer weights."""
    mk = L.Maker(key, cfg.jdtype)
    L.init_embed(mk, cfg)
    L.init_rmsnorm(mk, "final_norm", cfg.d_model)

    n_prefix, pat, n_sb = _scan_layout(cfg)

    # prefix (unscanned) layers
    for i in range(n_prefix):
        sub = mk.sub(f"prefix_{i}")
        _init_layer(sub, cfg, layer_kind(cfg, i))

    # scanned superblocks: one stacked tree per pattern position
    if n_sb > 0:
        def make_pos(j: int):
            kind = layer_kind(cfg, n_prefix + j)
            sub_mks = []
            for s in range(n_sb):
                smk = L.Maker(
                    jax.random.fold_in(key, 10_000 + j * 1000 + s), cfg.jdtype
                )
                if cfg.family == "audio":
                    _init_decoder_layer_for_audio(smk, cfg)
                else:
                    _init_layer(smk, cfg, kind)
                sub_mks.append(smk)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *[m.params for m in sub_mks])
            specs = jax.tree.map(
                lambda sp: (None,) + tuple(sp),
                sub_mks[0].specs,
                is_leaf=lambda x: isinstance(x, tuple),
            )
            return stacked, specs

        blocks, bspecs = {}, {}
        for j in range(pat):
            blocks[str(j)], bspecs[str(j)] = make_pos(j)
        mk.params["blocks"] = blocks
        mk.specs["blocks"] = bspecs

    # encoder (whisper) — the conv frontend is stubbed: inputs are frames
    if cfg.encoder is not None and cfg.family == "audio":
        enc_mks = []
        for s in range(cfg.encoder.n_layers):
            emk = L.Maker(jax.random.fold_in(key, 77_000 + s), cfg.jdtype)
            _init_layer(emk, cfg, "attn+mlp")
            enc_mks.append(emk)
        mk.params["encoder"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[m.params for m in enc_mks]
        )
        mk.specs["encoder"] = jax.tree.map(
            lambda sp: (None,) + tuple(sp),
            enc_mks[0].specs,
            is_leaf=lambda x: isinstance(x, tuple),
        )
        fmk = mk.sub("enc_norm")
        L.init_rmsnorm(fmk, "g", cfg.d_model)
    return mk.params, mk.specs


# ---------------------------------------------------------------------------
# forward building blocks
# ---------------------------------------------------------------------------
def _post(p: Params, cfg: ModelConfig, name: str, y: jax.Array) -> jax.Array:
    """A sublayer's output as the residual takes it: RMSNorm'd with
    sandwich norms, else as is."""
    return L.rmsnorm(p[name], y, cfg.norm_eps) if cfg.sandwich_norm else y


def ffn_sublayer(p: Params, cfg: ModelConfig, kind: str, x: jax.Array,
                 decoding: bool = False, valid: Optional[jax.Array] = None,
                 layer=None):
    """``x`` plus the feed-forward sublayer; returns ``(x, counts)``.

    ``counts`` is the held-expert layer's routed-token count (see
    :func:`repro.models.moe.moe_held`; only tokens where ``valid``),
    else None.  ``layer`` indexes held experts stacked over layers.
    """
    mixer, ffn = kind.split("+")
    if ffn == "none":
        return x, None
    h = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    counts = None
    if ffn in ("mlp", "dense_mlp"):
        if decoding and cfg.decode_mlp == "ws":
            y = L.mlp_ws_decode(p["mlp"], cfg, h)
        else:
            y = L.mlp(p["mlp"], h)
    elif cfg.moe.impl == "held":
        y, counts = M.moe_held(p["moe"], cfg, h, valid, layer)
    else:
        y = M.moe_block(p["moe"], cfg, h)
    return x + _post(p, cfg, "post_norm2", y), counts


def _apply_ffn(p: Params, cfg: ModelConfig, kind: str, x: jax.Array,
               decoding: bool = False) -> jax.Array:
    return ffn_sublayer(p, cfg, kind, x, decoding)[0]


def _apply_layer_full(
    p: Params,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    enc_kv=None,
    causal: bool = True,
    li: int = 0,
):
    """Full-sequence layer ``li`` (its window and rotary); returns
    (x, cache_contrib)."""
    mixer, _ = kind.split("+")
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    cache = None
    if mixer == "attn":
        out, kv = L.attention_full(
            p["attn"], cfg, h, positions, causal=causal,
            use_rope=cfg.layer_rope(li), window=cfg.layer_window(li),
        )
        cache = kv
        x = x + _post(p, cfg, "post_norm1", out)
    elif mixer == "mla":
        out, ckv = L.mla_full(p["attn"], cfg, h, positions)
        cache = ckv
        x = x + out
    elif mixer == "cross":
        x = x + L.cross_attention(p["attn"], cfg, h, enc_kv)
    elif mixer == "mamba":
        out, st = S.mamba_full(p["mamba"], cfg, h)
        cache = st
        x = x + out
    elif mixer == "mlstm":
        out, st = S.mlstm_full(p["cell"], cfg, h)
        cache = st
        x = x + out
    elif mixer == "slstm":
        out, st = S.slstm_full(p["cell"], cfg, h)
        cache = st
        x = x + out
    x = _apply_ffn(p, cfg, kind, x)
    x = constrain(x, ("batch", "seq_act", None))
    return x, cache


def _apply_layer_decode(
    p: Params,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    cache: Any,
    lengths: jax.Array,
    enc_kv=None,
    li: int = 0,
):
    mixer, _ = kind.split("+")
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    new_cache = cache
    if mixer == "attn":
        attn = dict(use_rope=cfg.layer_rope(li), window=cfg.layer_window(li))
        if cfg.kv_cache_dtype == "int8":
            out, new_cache = L.attention_decode_q8(
                p["attn"], cfg, h, cache, lengths, **attn)
        else:
            out, ck, cv = L.attention_decode(
                p["attn"], cfg, h, cache["k"], cache["v"], lengths, **attn
            )
            new_cache = {"k": ck, "v": cv}
        x = x + _post(p, cfg, "post_norm1", out)
    elif mixer == "mla":
        out, cc, cr = _mla_decode_absorbed(p["attn"], cfg, h, cache, lengths)
        new_cache = {"c_kv": cc, "k_rope": cr}
        x = x + out
    elif mixer == "cross":
        x = x + L.cross_attention(p["attn"], cfg, h, enc_kv)
    elif mixer == "mamba":
        out, st = S.mamba_decode(p["mamba"], cfg, h, cache)
        new_cache = st
        x = x + out
    elif mixer == "mlstm":
        out, st = S.mlstm_full(p["cell"], cfg, h, state=cache)
        new_cache = st
        x = x + out
    elif mixer == "slstm":
        out, st = S.slstm_full(p["cell"], cfg, h, state=cache)
        new_cache = st
        x = x + out
    x = _apply_ffn(p, cfg, kind, x, decoding=True)
    x = constrain(x, ("dec_batch", None, None))
    return x, new_cache


def _mla_decode_absorbed(p, cfg, x, cache, lengths):
    """MLA decode with weight absorption: attention runs in the compressed
    latent space; the per-head K/V are never materialized (the key MLA
    serving optimization — cache stays (S, lora+rope))."""
    m = cfg.mla
    B = x.shape[0]
    h = cfg.n_heads
    q_nope, q_rope, c_kv_new, k_rope_new = L._mla_qkv(p, cfg, x, lengths[:, None])
    cache_c, cache_r = cache["c_kv"], cache["k_rope"]
    onehot = jax.nn.one_hot(lengths, cache_c.shape[1], dtype=cache_c.dtype)
    cache_c = cache_c + onehot[:, :, None] * c_kv_new.astype(cache_c.dtype)
    cache_r = cache_r + onehot[:, :, None] * k_rope_new.astype(cache_r.dtype)
    # absorb W_uk into q:  q' = q_nope @ W_uk^T  -> latent space
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.nope_head_dim)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], w_uk)        # (B,H,lora)
    S_max = cache_c.shape[1]
    logits = jnp.einsum("bhl,bsl->bhs", q_lat.astype(jnp.float32),
                        cache_c.astype(jnp.float32))
    logits += jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(jnp.float32),
                         cache_r.astype(jnp.float32))
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    mask = jnp.arange(S_max)[None, :] < (lengths + 1)[:, None]
    logits = logits * scale + jnp.where(mask, 0.0, -1e30)[:, None, :]
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhs,bsl->bhl", w, cache_c.astype(jnp.float32))  # latent ctx
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhl,lhv->bhv", ctx, w_uv)                  # (B,H,v)
    out = out.reshape(B, 1, h * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return out, cache_c, cache_r


# ---------------------------------------------------------------------------
# encoder (whisper) / cross-kv precompute (vlm + audio)
# ---------------------------------------------------------------------------
def encode(params: Params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder over precomputed (stub) frame embeddings."""
    B, Se, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(Se)[None], (B, Se))
    x = frames

    def body(x, p):
        x, _ = _apply_layer_full(p, cfg, "attn+mlp", x, pos, causal=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.rmsnorm(params["enc_norm"]["g"], x, cfg.norm_eps)


def _cross_kvs(params: Params, cfg: ModelConfig, enc_out: jax.Array):
    """Precompute cross-attn K/V for every cross layer (stacked)."""
    n_prefix, pat, n_sb = _scan_layout(cfg)
    out = {}
    if cfg.family == "audio":
        for j in range(pat):
            p = params["blocks"][str(j)]
            k, v = jax.vmap(
                lambda pj: L.cross_kv(pj["xattn"], cfg, enc_out)
            )(p)
            out[str(j)] = (k, v)
        return out
    for j in range(pat):
        if layer_kind(cfg, n_prefix + j).startswith("cross"):
            p = params["blocks"][str(j)]
            k, v = jax.vmap(lambda pj: L.cross_kv(pj["attn"], cfg, enc_out))(p)
            out[str(j)] = (k, v)
    return out


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,                       # (B, S)
    enc_input: Optional[jax.Array] = None,   # vlm patches / audio frames
    collect_cache: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """Returns (logits (B,S,V), cache or None)."""
    B, Sq = tokens.shape
    x = L.embed_tokens(params, cfg, tokens)
    x = constrain(x, ("batch", None, None))
    positions = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq))

    enc_out = None
    cross = {}
    if cfg.family == "audio":
        enc_out = encode(params, cfg, enc_input)
        cross = _cross_kvs(params, cfg, enc_out)
    elif cfg.family == "vlm" and enc_input is not None:
        cross = _cross_kvs(params, cfg, enc_input.astype(cfg.jdtype))

    n_prefix, pat, n_sb = _scan_layout(cfg)
    caches: Dict[str, Any] = {}

    for i in range(n_prefix):
        kind = layer_kind(cfg, i)
        x, c = _apply_layer_full(params[f"prefix_{i}"], cfg, kind, x, positions,
                                 li=i)
        if collect_cache and c is not None:
            caches[f"prefix_{i}"] = c

    if n_sb > 0:
        kinds = [layer_kind(cfg, n_prefix + j) for j in range(pat)]
        if cfg.family == "audio":
            kinds = ["audio_dec"] * pat

        xs = {}
        for j in range(pat):
            blk = dict(params["blocks"][str(j)])
            if cfg.family == "audio" or str(j) in cross:
                blk["__cross_k"], blk["__cross_v"] = cross[str(j)]
            xs[str(j)] = blk

        def body(x, xs):
            new_caches = {}
            for j in range(pat):
                p = xs[str(j)]
                kv = None
                if "__cross_k" in p:
                    kv = (p["__cross_k"], p["__cross_v"])
                if cfg.family == "audio":
                    x2, c = _audio_dec_layer_full(p, cfg, x, positions, kv)
                    new_caches[str(j)] = c
                else:
                    x2, c = _apply_layer_full(
                        p, cfg, kinds[j], x, positions, enc_kv=kv,
                        li=n_prefix + j,
                    )
                    if c is not None:
                        new_caches[str(j)] = c
                x = x2
            return x, (new_caches if collect_cache else None)

        if cfg.remat == "full":
            body = jax.checkpoint(body)
        x, scan_caches = jax.lax.scan(body, x, xs)
        if collect_cache and scan_caches is not None:
            caches["blocks"] = scan_caches

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params, x, cfg.tie_embeddings)
    logits = constrain(logits, ("batch", None, "vocab_act"))
    out_cache = caches if collect_cache else None
    if collect_cache and enc_out is not None:
        out_cache["__enc_out"] = enc_out
    return logits, out_cache


def _audio_dec_layer_full(p, cfg, x, positions, enc_kv):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    out, kv = L.attention_full(p["attn"], cfg, h, positions, causal=True)
    x = x + out
    hx = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
    x = x + L.cross_attention(p["xattn"], cfg, hx, enc_kv)
    h2 = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h2)
    return x, kv


def _audio_dec_layer_decode(p, cfg, x, cache, lengths, enc_kv):
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    out, ck, cv = L.attention_decode(p["attn"], cfg, h, cache["k"], cache["v"], lengths)
    x = x + out
    hx = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
    x = x + L.cross_attention(p["xattn"], cfg, hx, enc_kv)
    h2 = L.rmsnorm(p["norm2"], x, cfg.norm_eps)
    x = x + L.mlp(p["mlp"], h2)
    return x, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# KV cache allocation + decode
# ---------------------------------------------------------------------------
def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    enc_input: Optional[jax.Array] = None,
    params: Optional[Params] = None,
) -> Dict[str, Any]:
    """Allocate empty caches (+ precomputed cross K/V when params given)."""
    dt = cfg.jdtype
    K, hd = cfg.n_kv_heads, cfg.hd
    n_prefix, pat, n_sb = _scan_layout(cfg)

    def attn_cache(lead=()):
        if cfg.kv_cache_dtype == "int8":
            return {
                "k": jnp.zeros(lead + (batch, max_len, K, hd), jnp.int8),
                "v": jnp.zeros(lead + (batch, max_len, K, hd), jnp.int8),
                "k_s": jnp.zeros(lead + (batch, max_len, K), jnp.float32),
                "v_s": jnp.zeros(lead + (batch, max_len, K), jnp.float32),
            }
        return {
            "k": jnp.zeros(lead + (batch, max_len, K, hd), dt),
            "v": jnp.zeros(lead + (batch, max_len, K, hd), dt),
        }

    def mla_cache(lead=()):
        m = cfg.mla
        return {
            "c_kv": jnp.zeros(lead + (batch, max_len, m.kv_lora_rank), dt),
            "k_rope": jnp.zeros(lead + (batch, max_len, m.rope_head_dim), dt),
        }

    caches: Dict[str, Any] = {"lengths": jnp.zeros((batch,), jnp.int32)}
    for i in range(n_prefix):
        mixer = layer_kind(cfg, i).split("+")[0]
        if mixer == "attn":
            caches[f"prefix_{i}"] = attn_cache()
        elif mixer == "mla":
            caches[f"prefix_{i}"] = mla_cache()
        elif mixer == "mamba":
            caches[f"prefix_{i}"] = S.mamba_init_state(cfg, batch, dt)
    if n_sb > 0:
        blocks = {}
        for j in range(pat):
            if cfg.family == "audio":
                blocks[str(j)] = attn_cache((n_sb,))
                continue
            mixer = layer_kind(cfg, n_prefix + j).split("+")[0]
            if mixer == "attn":
                blocks[str(j)] = attn_cache((n_sb,))
            elif mixer == "cross":
                blocks[str(j)] = {}  # cross K/V live in __cross (static)
            elif mixer == "mla":
                blocks[str(j)] = mla_cache((n_sb,))
            elif mixer == "mamba":
                st = S.mamba_init_state(cfg, batch, dt)
                blocks[str(j)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (n_sb,) + a.shape), st
                )
            elif mixer in ("mlstm", "slstm"):
                st = S.xlstm_init_state(cfg, batch, mixer == "slstm")
                blocks[str(j)] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (n_sb,) + a.shape), st
                )
        caches["blocks"] = blocks
    if cfg.family in ("audio", "vlm"):
        if params is not None and enc_input is not None:
            enc_out = (
                encode(params, cfg, enc_input)
                if cfg.family == "audio"
                else enc_input.astype(cfg.jdtype)
            )
            caches["__cross"] = _cross_kvs(params, cfg, enc_out)
        else:
            # stub cross K/V (dry-run decode: filled by prefill in real runs)
            Se = cfg.encoder.n_ctx if cfg.encoder is not None else 0
            cross: Dict[str, Any] = {}
            for j in range(pat):
                is_cross = cfg.family == "audio" or layer_kind(
                    cfg, n_prefix + j
                ).startswith("cross")
                if is_cross and Se:
                    kv_shape = (n_sb, batch, Se, K, hd)
                    cross[str(j)] = (
                        jnp.zeros(kv_shape, dt),
                        jnp.zeros(kv_shape, dt),
                    )
            if cross:
                caches["__cross"] = cross
    return caches


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    max_len: int,
    enc_input: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the prompt, build the decode cache.  Returns (last_logits, cache)."""
    B, Sq = tokens.shape
    logits, run_cache = forward(
        params, cfg, tokens, enc_input=enc_input, collect_cache=True
    )
    cache = init_cache(cfg, B, max_len, enc_input=enc_input, params=params)
    cache["lengths"] = jnp.full((B,), Sq, jnp.int32)

    # place prefill K/V into the slot caches
    def place_attn(dst, src):  # src (…, B, Sq, K, hd) -> dst (…, B, max, K, hd)
        return jax.lax.dynamic_update_slice_in_dim(dst, src.astype(dst.dtype), 0, axis=-3)

    def place_attn_q8(dst_blk, src_k, src_v):
        kq, ks = L._q8_kv(src_k)
        vq, vs = L._q8_kv(src_v)
        out = dict(dst_blk)
        out["k"] = jax.lax.dynamic_update_slice_in_dim(dst_blk["k"], kq, 0, axis=-3)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(dst_blk["v"], vq, 0, axis=-3)
        out["k_s"] = jax.lax.dynamic_update_slice_in_dim(dst_blk["k_s"], ks, 0, axis=-2)
        out["v_s"] = jax.lax.dynamic_update_slice_in_dim(dst_blk["v_s"], vs, 0, axis=-2)
        return out

    for key_, c in (run_cache or {}).items():
        if key_ == "__enc_out":
            continue
        if key_ == "blocks":
            for j, blk in c.items():
                dst = cache["blocks"][j]
                if "k" in blk and cfg.kv_cache_dtype == "int8":
                    cache["blocks"][j] = place_attn_q8(dst, blk["k"], blk["v"])
                elif "k" in blk:
                    dst["k"] = place_attn(dst["k"], blk["k"])
                    dst["v"] = place_attn(dst["v"], blk["v"])
                elif "c_kv" in blk:
                    dst["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
                        dst["c_kv"], blk["c_kv"].astype(dst["c_kv"].dtype), 0, axis=-2
                    )
                    dst["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
                        dst["k_rope"], blk["k_rope"].astype(dst["k_rope"].dtype), 0, axis=-2
                    )
                else:  # recurrent states: final state replaces init
                    cache["blocks"][j] = blk
        else:
            dst = cache[key_]
            if "k" in c and cfg.kv_cache_dtype == "int8":
                cache[key_] = place_attn_q8(dst, c["k"], c["v"])
            elif "k" in c:
                dst["k"] = place_attn(dst["k"], c["k"])
                dst["v"] = place_attn(dst["v"], c["v"])
            elif "c_kv" in c:
                dst["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
                    dst["c_kv"], c["c_kv"].astype(dst["c_kv"].dtype), 0, axis=-2
                )
                dst["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
                    dst["k_rope"], c["k_rope"].astype(dst["k_rope"].dtype), 0, axis=-2
                )
            else:
                cache[key_] = c
    return logits[:, -1], cache


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache: Dict[str, Any],
    tokens: jax.Array,                 # (B,) or (B,1)
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step for the whole batch; returns (logits (B,V), cache)."""
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    B = tokens.shape[0]
    lengths = cache["lengths"]
    x = L.embed_tokens(params, cfg, tokens)
    x = constrain(x, ("dec_batch", None, None))
    n_prefix, pat, n_sb = _scan_layout(cfg)
    cross = cache.get("__cross", {})
    new_cache: Dict[str, Any] = dict(cache)

    for i in range(n_prefix):
        kind = layer_kind(cfg, i)
        x, c = _apply_layer_decode(
            params[f"prefix_{i}"], cfg, kind, x, cache.get(f"prefix_{i}"), lengths,
            li=i,
        )
        new_cache[f"prefix_{i}"] = c

    if n_sb > 0:
        kinds = [layer_kind(cfg, n_prefix + j) for j in range(pat)]

        def body(x, xs):
            p_and_c = xs
            new_blk = {}
            for j in range(pat):
                p, c = p_and_c[str(j)]
                kv = None
                if "__cross_k" in p:
                    kv = (p["__cross_k"], p["__cross_v"])
                if cfg.family == "audio":
                    x2, nc = _audio_dec_layer_decode(p, cfg, x, c, lengths, kv)
                else:
                    x2, nc = _apply_layer_decode(
                        p, cfg, kinds[j], x, c, lengths, enc_kv=kv,
                        li=n_prefix + j,
                    )
                new_blk[str(j)] = nc
                x = x2
            return x, new_blk

        xs = {}
        for j in range(pat):
            blk = dict(params["blocks"][str(j)])
            if str(j) in cross:
                blk["__cross_k"], blk["__cross_v"] = cross[str(j)]
            xs[str(j)] = (blk, cache["blocks"][str(j)])
        x, nb = jax.lax.scan(body, x, xs)
        new_cache["blocks"] = nb

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.unembed(params, x, cfg.tie_embeddings)
    new_cache["lengths"] = lengths + 1
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------
def lm_loss(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    targets: jax.Array,
    enc_input: Optional[jax.Array] = None,
) -> jax.Array:
    logits, _ = forward(params, cfg, tokens, enc_input=enc_input)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
