"""The afmoe block (Arcee Trinity): weights, float32 reference, work counts.

Written from the published configuration (``model_type`` ``afmoe``) and
the family's description, not from the program; each point the
configuration's keys do not fix is listed under the configuration
file's ``assumed``.  Per layer:

- attention: ``a = RMSNorm(x)``; ``q, k, v`` projections (GQA); a
  per-head RMSNorm of ``q`` and of ``k`` over ``head_dim``, then rotary
  (halves rotated) on ``sliding_attention`` layers only (the
  ``full_attention`` layers have no positional encoding); causal
  softmax at ``head_dim ** -0.5``, sliding layers seeing the last
  ``sliding_window`` positions; the heads' output times
  ``sigmoid(a @ wg)``, then ``wo``; ``x += RMSNorm(that)``;
- feed-forward: ``b = RMSNorm(x)``; the first ``num_dense_layers`` run
  a SwiGLU of ``intermediate_size``, the others the expert layer;
  ``x += RMSNorm(FFN(b))``;
- expert layer: float32 scores ``s = sigmoid(b @ router)`` over the
  router's published ``num_experts``; the top ``num_experts_per_tok``
  of ``s + router_bias`` are chosen (the bias acts on the selection
  only), weighted ``s / sum(s) * route_scale``; each chosen expert is a
  SwiGLU of ``moe_intermediate_size``, plus ``num_shared_experts``
  shared ones for every token.  This chip holds the configuration's
  ``num_experts`` (experts ``0 ..``): the chosen experts held elsewhere
  add nothing here, in the program and in this reference alike.

Embeddings are scaled by ``sqrt(hidden_size)`` (``mup_enabled``); a
final RMSNorm and the untied head follow.  Attention, norms and SwiGLU
are the dense block's where they agree.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Tuple

from blocks import dense
from reference import Q_BLOCK, _mm


def dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The block's sizes, by short name, from the configuration file."""
    n = dense.dims(spec)
    every = spec["global_attn_every_n_layers"]
    n.update(
        window=spec["sliding_window"], every=every,
        sliding=[t == "sliding_attention" for t in spec["layer_types"]],
        dense=spec["num_dense_layers"], f=spec["moe_intermediate_size"],
        E=spec["published"]["num_experts"], held=spec["num_experts"],
        k=spec["num_experts_per_tok"], shared=spec["num_shared_experts"],
        scale=float(spec["route_scale"]),
    )
    # the program's layout: whole periods of the layer pattern that cover
    # the leading dense layers stay unscanned, the rest is stacked
    n["prefix"] = -(-n["dense"] // every) * every
    n["stack"] = (n["L"] - n["prefix"]) // every
    return n


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _layer_shapes(n, lead: Tuple[int, ...], moe: bool) -> Dict[str, Any]:
    d, q, kv, hd = n["d"], n["H"] * n["hd"], n["K"] * n["hd"], n["hd"]
    out = {
        "norm1": (lead + (d,), None), "norm2": (lead + (d,), None),
        "post_norm1": (lead + (d,), None), "post_norm2": (lead + (d,), None),
        "attn/wq": (lead + (d, q), 1 / math.sqrt(d)),
        "attn/wk": (lead + (d, kv), 1 / math.sqrt(d)),
        "attn/wv": (lead + (d, kv), 1 / math.sqrt(d)),
        "attn/wo": (lead + (q, d), 1 / math.sqrt(q)),
        "attn/wg": (lead + (d, q), 1 / math.sqrt(d)),
        "attn/q_norm": (lead + (hd,), None),
        "attn/k_norm": (lead + (hd,), None),
    }
    if not moe:
        ff = n["ff"]
        out.update({
            "mlp/w_gate": (lead + (d, ff), 1 / math.sqrt(d)),
            "mlp/w_up": (lead + (d, ff), 1 / math.sqrt(d)),
            "mlp/w_down": (lead + (ff, d), 1 / math.sqrt(ff)),
        })
        return out
    f, fs, E, h = n["f"], n["shared"] * n["f"], n["E"], n["held"]
    out.update({
        "moe/router": (lead + (d, E), 1 / math.sqrt(d)),
        "moe/router_bias": (lead + (E,), 1 / math.sqrt(d)),
        "moe/w_gate": (lead + (h, d, f), 1 / math.sqrt(d)),
        "moe/w_up": (lead + (h, d, f), 1 / math.sqrt(d)),
        "moe/w_down": (lead + (h, f, d), 1 / math.sqrt(f)),
        "moe/shared/w_gate": (lead + (d, fs), 1 / math.sqrt(d)),
        "moe/shared/w_up": (lead + (d, fs), 1 / math.sqrt(d)),
        "moe/shared/w_down": (lead + (fs, d), 1 / math.sqrt(fs)),
    })
    return out


def weight_shapes(spec) -> Dict[str, Any]:
    """``{path: (shape, std)}`` in the program's tree: ``prefix_<i>/...``
    for the unscanned leading layers, ``blocks/<j>/...`` stacked over
    the periods that follow (``std`` ``None`` for a norm's ones)."""
    n = dims(spec)
    out = dict(dense.outer_shapes(n))
    for i in range(n["prefix"]):
        for path, v in _layer_shapes(n, (), i >= n["dense"]).items():
            out[f"prefix_{i}/{path}"] = v
    for j in range(n["every"]):
        for path, v in _layer_shapes(n, (n["stack"],), True).items():
            out[f"blocks/{j}/{path}"] = v
    return out


# ---------------------------------------------------------------------------
# the float32 reference
# ---------------------------------------------------------------------------
def attention(x, p, pos, n, li: int, fp8: bool):
    """``x`` plus layer ``li``'s gated, sandwich-normed attention."""
    import jax
    import jax.numpy as jnp

    H, K, hd = n["H"], n["K"], n["hd"]
    S, g = x.shape[0], H // K
    sliding = n["sliding"][li]
    a = dense.rmsnorm(x, p["norm1"], n["eps"])
    # QK norm: RMSNorm over each head's last axis
    q = dense.rmsnorm(_mm(a, p["attn"]["wq"], fp8).reshape(S, H, hd),
                      p["attn"]["q_norm"], n["eps"])
    k = dense.rmsnorm(_mm(a, p["attn"]["wk"], fp8).reshape(S, K, hd),
                      p["attn"]["k_norm"], n["eps"])
    v = _mm(a, p["attn"]["wv"], fp8).reshape(S, K, hd)
    if sliding:
        q, k = dense.rope(q, pos, n["theta"]), dense.rope(k, pos, n["theta"])
    q = q * (hd ** -0.5)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK].reshape(-1, K, g, hd)
        m = qb.shape[0]
        qpos = (s0 + jnp.arange(m))[:, None]
        logits = jnp.einsum("qkgd,skd->kgqs", qb, k)
        mask = qpos >= pos[None, :]
        if sliding:
            mask &= pos[None, :] > qpos - n["window"]
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
        att = jax.nn.softmax(logits, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", att, v).reshape(m, H * hd))
    o = jnp.concatenate(outs, axis=0) * jax.nn.sigmoid(_mm(a, p["attn"]["wg"], fp8))
    return x + dense.rmsnorm(_mm(o, p["attn"]["wo"], fp8), p["post_norm1"], n["eps"])


def experts(b, p, n, fp8: bool):
    """This chip's share of the expert layer plus the shared experts."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(_mm(b, p["router"], fp8))                     # (S, E)
    _, ids = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), n["k"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * n["scale"]
    gate = jnp.zeros(s.shape[:1] + (n["E"] + 1,), jnp.float32)
    here = jnp.where(ids < n["held"], ids, n["E"])                  # held: 0 ..
    gate = gate.at[jnp.arange(b.shape[0])[:, None], here].add(w)[:, :n["held"]]
    each = jax.vmap(lambda g, u, dn: dense.swiglu(
        b, {"w_gate": g, "w_up": u, "w_down": dn}, fp8))(
        p["w_gate"], p["w_up"], p["w_down"])                         # (held, S, d)
    return jnp.einsum("esd,se->sd", each, gate) + dense.swiglu(b, p["shared"], fp8)


def layer(x, p, pos, n, li: int, fp8: bool):
    x = attention(x, p, pos, n, li, fp8)
    b = dense.rmsnorm(x, p["norm2"], n["eps"])
    y = dense.swiglu(b, p["mlp"], fp8) if li < n["dense"] else experts(b, p["moe"], n, fp8)
    return x + dense.rmsnorm(y, p["post_norm2"], n["eps"])


def forward(w, tokens, read_at, *, spec, fp8: bool):
    """Logits (float32) at positions ``read_at`` of one padded sequence."""
    import jax
    import jax.numpy as jnp

    n = dims(spec)
    pos = jnp.arange(tokens.shape[0])
    x = jnp.take(w["embedding"], tokens, axis=0).astype(jnp.float32) * math.sqrt(n["d"])
    for i in range(n["prefix"]):
        x = layer(x, w[f"prefix_{i}"], pos, n, i, fp8)

    def period(x, ps):
        for j in range(n["every"]):
            x = layer(x, ps[str(j)], pos, n, n["prefix"] + j, fp8)
        return x, None

    x, _ = jax.lax.scan(period, x, w["blocks"])
    x = dense.rmsnorm(jnp.take(x, read_at, axis=0), w["final_norm"], n["eps"])
    return _mm(x, w["lm_head"], fp8)


# ---------------------------------------------------------------------------
# work counts: what the model's mathematics needs for the recorded shapes
# ---------------------------------------------------------------------------
def attention_work(spec, contexts: Iterable[int],
                   kv_bytes: int = 2) -> Tuple[float, float]:
    """FLOPs and K/V bytes of one decode token per row, all layers.

    A sliding layer's query attends to at most ``sliding_window`` keys,
    so its work and the bytes it needs stop there; a full layer's take
    the whole context.  QK^T and PV each take 2 * heads * head_dim
    FLOPs per key; each key's K and V are read once per layer.
    """
    n = dims(spec)
    contexts = [int(c) for c in contexts]
    keys = sum(min(c, n["window"]) if sl else c
               for sl in n["sliding"] for c in contexts)
    return 4.0 * n["H"] * n["hd"] * keys, 2.0 * n["K"] * n["hd"] * kv_bytes * keys


def routed_to_held(spec, tokens: int) -> float:
    """Expected assignments per expert layer to the experts held here:
    tokens × experts per token × held / the router's experts."""
    n = dims(spec)
    return tokens * n["k"] * n["held"] / n["E"]


def expert_work(spec, tokens: int) -> Tuple[float, float]:
    """FLOPs and weight bytes of the held experts for ``tokens`` tokens,
    all expert layers, in expectation.

    FLOPs are the SwiGLU's 2 * 3 * hidden * expert width per routed
    assignment; the bytes are the held experts' weights that at least
    one of the tokens touches (each expert is missed by all of them
    with probability ``(1 - k/E) ** tokens``), read once.
    """
    n = dims(spec)
    per_expert = 3 * n["d"] * n["f"]
    n_moe = n["L"] - n["dense"]
    flops = 2.0 * per_expert * routed_to_held(spec, tokens) * n_moe
    touched = 1.0 - (1.0 - n["k"] / n["E"]) ** int(tokens)
    return flops, 2.0 * per_expert * n["held"] * touched * n_moe


def layer_params(spec) -> float:
    """Weights a token multiplies in the layers, in expectation: attention
    and its gate, the dense MLPs, the router, the shared experts and
    the held experts it is routed to."""
    n = dims(spec)
    d, q = n["d"], n["H"] * n["hd"]
    attn = dense.attention_params(n) + d * q
    n_moe = n["L"] - n["dense"]
    moe = d * n["E"] + 3 * d * n["f"] * (n["shared"] + n["k"] * n["held"] / n["E"])
    return n["L"] * attn + n["dense"] * 3 * d * n["ff"] + n_moe * moe


def decode_step_flops(spec, contexts) -> float:
    """Model FLOPs of one decode step over the real rows (padding excluded)."""
    n = dims(spec)
    contexts = list(contexts)
    attn, _ = attention_work(spec, contexts)
    return 2.0 * (layer_params(spec) + n["d"] * n["V"]) * len(contexts) + attn


def prefill_chunk_flops(spec, past: int, chunk: int) -> float:
    """Model FLOPs of one prefill chunk; the head counts once per chunk."""
    n = dims(spec)
    attn, _ = attention_work(spec, [past + i + 1 for i in range(chunk)])
    return 2.0 * (layer_params(spec) * chunk + n["d"] * n["V"]) + attn
