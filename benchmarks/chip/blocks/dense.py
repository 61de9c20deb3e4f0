"""The dense decoder block: weights, float32 reference and work counts.

The block the repository's dense family runs: RMSNorm (gain only),
rotary positions on the whole head (halves rotated), grouped-query
attention with full causal attention on every layer, SwiGLU MLP, an
untied head.  ``configs/*.json`` lists where a published model departs
from it.

Every number comes from the configuration file's published keys
(``num_hidden_layers``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
``vocab_size``, ``rms_norm_eps``, ``rope_theta``); nothing of the
program is imported.  A configuration without a ``"block"`` key uses
this module.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Tuple

from reference import Q_BLOCK, _mm


def dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The block's sizes, by short name, from the configuration file."""
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    return {"L": spec["num_hidden_layers"], "d": d, "H": H,
            "K": spec["num_key_value_heads"], "hd": spec.get("head_dim") or d // H,
            "ff": spec["intermediate_size"], "V": spec["vocab_size"],
            "eps": float(spec["rms_norm_eps"]), "theta": float(spec["rope_theta"])}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def attention_shapes(n: Dict[str, Any], prefix: str) -> Dict[str, Any]:
    """Norms and attention projections of ``L`` stacked layers."""
    L, d = n["L"], n["d"]
    q, kv = n["H"] * n["hd"], n["K"] * n["hd"]
    return {
        f"{prefix}/norm1": ((L, d), None),
        f"{prefix}/norm2": ((L, d), None),
        f"{prefix}/attn/wq": ((L, d, q), 1 / math.sqrt(d)),
        f"{prefix}/attn/wk": ((L, d, kv), 1 / math.sqrt(d)),
        f"{prefix}/attn/wv": ((L, d, kv), 1 / math.sqrt(d)),
        f"{prefix}/attn/wo": ((L, q, d), 1 / math.sqrt(q)),
    }


def outer_shapes(n: Dict[str, Any]) -> Dict[str, Any]:
    """Embedding, final norm and head."""
    d, V = n["d"], n["V"]
    return {
        "embedding": ((V, d), 1 / math.sqrt(d)),
        "lm_head": ((d, V), 1 / math.sqrt(d)),
        "final_norm": ((d,), None),
    }


def weight_shapes(spec) -> Dict[str, Any]:
    """Layout and initial scale of every weight of a dense decoder.

    Returns ``{path: (shape, std)}``; ``std`` of ``None`` means ones (a
    norm's gain).  Paths follow the parameter tree the program's paged
    model reads: ``blocks/0/...`` leaves are stacked over layers.
    """
    n = dims(spec)
    L, d, ff = n["L"], n["d"], n["ff"]
    return {
        **outer_shapes(n),
        **attention_shapes(n, "blocks/0"),
        "blocks/0/mlp/w_gate": ((L, d, ff), 1 / math.sqrt(d)),
        "blocks/0/mlp/w_up": ((L, d, ff), 1 / math.sqrt(d)),
        "blocks/0/mlp/w_down": ((L, ff, d), 1 / math.sqrt(ff)),
    }


# ---------------------------------------------------------------------------
# the float32 reference
# ---------------------------------------------------------------------------
def rmsnorm(x, g, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def rope(x, pos, theta):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, pos, n, fp8: bool):
    """``x`` plus causal GQA self-attention over the whole sequence,
    a block of ``Q_BLOCK`` queries at a time."""
    import jax
    import jax.numpy as jnp

    H, K, hd = n["H"], n["K"], n["hd"]
    S = x.shape[0]
    g = H // K
    h = rmsnorm(x, p["norm1"], n["eps"])
    q = _mm(h, p["attn"]["wq"], fp8).reshape(S, H, hd)
    k = _mm(h, p["attn"]["wk"], fp8).reshape(S, K, hd)
    v = _mm(h, p["attn"]["wv"], fp8).reshape(S, K, hd)
    q = rope(q, pos, n["theta"]) * (hd ** -0.5)
    k = rope(k, pos, n["theta"])
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK].reshape(-1, K, g, hd)
        m = qb.shape[0]
        logits = jnp.einsum("qkgd,skd->kgqs", qb, k)
        mask = (s0 + jnp.arange(m))[:, None] >= pos[None, :]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        att = jax.nn.softmax(logits, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", att, v).reshape(m, H * hd))
    return x + _mm(jnp.concatenate(outs, axis=0), p["attn"]["wo"], fp8)


def swiglu(h, p, fp8: bool):
    import jax

    return _mm(jax.nn.silu(_mm(h, p["w_gate"], fp8)) * _mm(h, p["w_up"], fp8),
               p["w_down"], fp8)


def forward(w, tokens, read_at, *, spec, fp8: bool):
    """Logits (float32) at positions ``read_at`` of one padded sequence."""
    import jax
    import jax.numpy as jnp

    n = dims(spec)
    pos = jnp.arange(tokens.shape[0])
    x = jnp.take(w["embedding"], tokens, axis=0).astype(jnp.float32)

    def layer(x, p):
        x = attention(x, p, pos, n, fp8)
        h = rmsnorm(x, p["norm2"], n["eps"])
        return x + swiglu(h, p["mlp"], fp8), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["0"])
    x = rmsnorm(jnp.take(x, read_at, axis=0), w["final_norm"], n["eps"])
    return _mm(x, w["lm_head"], fp8)


# ---------------------------------------------------------------------------
# work counts: what the model's mathematics needs for the recorded shapes
# ---------------------------------------------------------------------------
def attention_params(n: Dict[str, Any]) -> int:
    """Attention weights every token multiplies in one layer."""
    d, q, kv = n["d"], n["H"] * n["hd"], n["K"] * n["hd"]
    return d * q + 2 * d * kv + q * d


def layer_params(spec) -> int:
    """Weights every token multiplies in the layers: attention and MLP."""
    n = dims(spec)
    return n["L"] * (attention_params(n) + 3 * n["d"] * n["ff"])


def attention_work(spec, contexts: Iterable[int],
                   kv_bytes: int = 2) -> Tuple[float, float]:
    """FLOPs and K/V bytes of one decode token per row, all layers.

    ``contexts`` are the tokens each query attends to (its own
    included).  QK^T and PV each take 2 * heads * head_dim FLOPs per
    context token; the bytes are each live token's K and V, read once
    per layer for the whole group of query heads.
    """
    n = dims(spec)
    ctx = sum(int(c) for c in contexts)
    flops = 4.0 * n["H"] * n["hd"] * ctx * n["L"]
    bytes_ = 2.0 * n["K"] * n["hd"] * kv_bytes * ctx * n["L"]
    return flops, bytes_


def decode_step_flops(spec, contexts) -> float:
    """Model FLOPs of one decode step over the real rows (padding excluded)."""
    n = dims(spec)
    contexts = list(contexts)
    attn, _ = attention_work(spec, contexts)
    head = n["d"] * n["V"]
    return 2.0 * (layer_params(spec) + head) * len(contexts) + attn


def prefill_chunk_flops(spec, past: int, chunk: int) -> float:
    """Model FLOPs of one prefill chunk: ``chunk`` queries after ``past``.

    Only the prompt's last token needs the head; it counts once per
    chunk (the program computes it for every token of the chunk, which
    is not model work).
    """
    n = dims(spec)
    ctx = [past + i + 1 for i in range(chunk)]
    attn, _ = attention_work(spec, ctx)
    return 2.0 * (layer_params(spec) * chunk + n["d"] * n["V"]) + attn
