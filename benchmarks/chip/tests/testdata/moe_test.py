"""A test block: GQA attention with routed and shared SwiGLU experts.

Written from the published description of a DeepSeek-style expert
layer, not from the program: a softmax router over
``n_routed_experts``, the ``num_experts_per_tok`` largest
probabilities renormalised to sum to one, each chosen expert a SwiGLU
of width ``moe_intermediate_size``, and ``n_shared_experts`` SwiGLU
experts (one of ``n_shared_experts`` times that width) added for every
token.  Attention, norms and head are the dense block's.  It shows that
a block other than ``dense`` needs new files only; it is not a
configuration of the benchmark.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from blocks import dense
from reference import _mm

attention_work = dense.attention_work


def weight_shapes(spec) -> Dict[str, Any]:
    n = dense.dims(spec)
    L, d = n["L"], n["d"]
    E, f = spec["n_routed_experts"], spec["moe_intermediate_size"]
    fs = spec["n_shared_experts"] * f
    return {
        **dense.outer_shapes(n),
        **dense.attention_shapes(n, "blocks/0"),
        "blocks/0/moe/router": ((L, d, E), 1 / math.sqrt(d)),
        "blocks/0/moe/w_gate": ((L, E, d, f), 1 / math.sqrt(d)),
        "blocks/0/moe/w_up": ((L, E, d, f), 1 / math.sqrt(d)),
        "blocks/0/moe/w_down": ((L, E, f, d), 1 / math.sqrt(f)),
        "blocks/0/moe/shared/w_gate": ((L, d, fs), 1 / math.sqrt(d)),
        "blocks/0/moe/shared/w_up": ((L, d, fs), 1 / math.sqrt(d)),
        "blocks/0/moe/shared/w_down": ((L, fs, d), 1 / math.sqrt(fs)),
    }


def experts(h, p, top_k: int, fp8: bool):
    """Routed experts' gate-weighted sum plus the shared expert."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(_mm(h, p["router"], fp8), axis=-1)      # (S, E)
    top, ids = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], ids].set(top)
    each = jax.vmap(lambda g, u, dn: dense.swiglu(
        h, {"w_gate": g, "w_up": u, "w_down": dn}, fp8))(
        p["w_gate"], p["w_up"], p["w_down"])                         # (E, S, d)
    return jnp.einsum("esd,se->sd", each, gate) + dense.swiglu(h, p["shared"], fp8)


def forward(w, tokens, read_at, *, spec, fp8: bool):
    import jax
    import jax.numpy as jnp

    n = dense.dims(spec)
    pos = jnp.arange(tokens.shape[0])
    x = jnp.take(w["embedding"], tokens, axis=0).astype(jnp.float32)

    def layer(x, p):
        x = dense.attention(x, p, pos, n, fp8)
        h = dense.rmsnorm(x, p["norm2"], n["eps"])
        return x + experts(h, p["moe"], spec["num_experts_per_tok"], fp8), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["0"])
    x = dense.rmsnorm(jnp.take(x, read_at, axis=0), w["final_norm"], n["eps"])
    return _mm(x, w["lm_head"], fp8)


def layer_params(spec) -> int:
    """Weights a token multiplies in the layers: attention, router, its
    routed experts and the shared ones."""
    n = dense.dims(spec)
    active = spec["num_experts_per_tok"] + spec["n_shared_experts"]
    return n["L"] * (dense.attention_params(n) + n["d"] * spec["n_routed_experts"]
                     + 3 * n["d"] * spec["moe_intermediate_size"] * active)


def decode_step_flops(spec, contexts) -> float:
    n = dense.dims(spec)
    contexts = list(contexts)
    attn, _ = attention_work(spec, contexts)
    return 2.0 * (layer_params(spec) + n["d"] * n["V"]) * len(contexts) + attn


def prefill_chunk_flops(spec, past: int, chunk: int) -> float:
    n = dense.dims(spec)
    attn, _ = attention_work(spec, [past + i + 1 for i in range(chunk)])
    return 2.0 * (layer_params(spec) * chunk + n["d"] * n["V"]) + attn
