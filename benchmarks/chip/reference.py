"""Plain float32 reference of the served decoder, and its fp8 control.

Imports nothing of the program.  It takes the weights the harness made
from the seed (in the layout the program reads), the prompts and the
served tokens, and computes the full causal forward pass in float32 at
``highest`` matmul precision: RMSNorm (gain only), rotary positions on
the whole head (halves rotated), grouped-query attention, SwiGLU MLP,
an untied head.  That is the block the repository's dense family runs;
``configs/*.json`` lists where it departs from a published model.

Each sequence runs as one call over all layers, padded at its end to a
bucket length (causal attention never looks ahead, so the padding
changes nothing before it).  Attention is computed a block of queries
at a time, so that a long sequence fits beside the weights.

The control (``precision="fp8"``) is the same forward pass with every
linear layer's operands rounded to float8 e4m3 (per-tensor scale for
weights, per-row scale for activations), accumulated in float32: the
step below the bf16 the configurations state.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence, Tuple

import numpy as np

Q_BLOCK = 512


def _q8(x, axis):
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, fp8: bool):
    import jax.numpy as jnp

    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        a = _q8(a, axis=-1)
        w = _q8(w, axis=None)
    return a @ w


def _rmsnorm(x, g, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _forward(w, tokens, read_at, *, dims, fp8: bool):
    """Logits (float32) at positions ``read_at`` of one padded sequence."""
    import jax
    import jax.numpy as jnp

    H, K, hd, eps, theta = dims
    S = tokens.shape[0]
    g = H // K
    pos = jnp.arange(S)
    x = jnp.take(w["embedding"], tokens, axis=0).astype(jnp.float32)

    def layer(x, p):
        h = _rmsnorm(x, p["norm1"], eps)
        q = _mm(h, p["attn"]["wq"], fp8).reshape(S, H, hd)
        k = _mm(h, p["attn"]["wk"], fp8).reshape(S, K, hd)
        v = _mm(h, p["attn"]["wv"], fp8).reshape(S, K, hd)
        q = _rope(q, pos, theta) * (hd ** -0.5)
        k = _rope(k, pos, theta)
        outs = []
        for s0 in range(0, S, Q_BLOCK):
            qb = q[s0:s0 + Q_BLOCK].reshape(-1, K, g, hd)
            n = qb.shape[0]
            logits = jnp.einsum("qkgd,skd->kgqs", qb, k)
            mask = (s0 + jnp.arange(n))[:, None] >= pos[None, :]
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
            att = jax.nn.softmax(logits, axis=-1)
            outs.append(jnp.einsum("kgqs,skd->qkgd", att, v).reshape(n, H * hd))
        x = x + _mm(jnp.concatenate(outs, axis=0), p["attn"]["wo"], fp8)
        h = _rmsnorm(x, p["norm2"], eps)
        a = jax.nn.silu(_mm(h, p["mlp"]["w_gate"], fp8)) * _mm(h, p["mlp"]["w_up"], fp8)
        return x + _mm(a, p["mlp"]["w_down"], fp8), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["0"])
    x = _rmsnorm(jnp.take(x, read_at, axis=0), w["final_norm"], eps)
    return _mm(x, w["lm_head"], fp8)


_JITS: Dict[Tuple, object] = {}


def _fn(dims, fp8: bool):
    import jax

    key = (dims, fp8)
    if key not in _JITS:
        _JITS[key] = jax.jit(partial(_forward, dims=dims, fp8=fp8))
    return _JITS[key]


def bucket(n: int, cap: int) -> int:
    b = 256
    while b < n:
        b *= 2
    return min(b, cap)


def logit_gaps(weights, cfg, seqs: Sequence[Tuple[List[int], List[int]]],
               cap: int, control: bool = False) -> Dict[str, object]:
    """Gaps of the served tokens below the reference's best logit.

    ``seqs`` holds ``(prompt, served_tokens)`` pairs.  For each served
    token the gap is ``max(ref_logits) - ref_logits[token]`` at the
    position that predicts it; 0 where the reference agrees.  With
    ``control``, also the gap of the token the fp8 control puts first
    at each of those positions.

    Returns ``{"max_gap", "tokens", "control_max_gap"?}``.
    """
    import jax
    import jax.numpy as jnp

    dims = (cfg.n_heads, cfg.n_kv_heads, cfg.hd, float(cfg.norm_eps),
            float(cfg.rope_theta))
    worst, worst_ctl, n_tok = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for prompt, served in seqs:
            if not served:
                continue
            seq = list(prompt) + list(served[:-1])
            S = bucket(len(seq), cap)
            toks = np.zeros((S,), np.int32)
            toks[: len(seq)] = seq
            read = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
            r = np.zeros((S,), np.int32)          # one compile per bucket
            r[: len(read)] = read
            ref = _fn(dims, False)(weights, jnp.asarray(toks), jnp.asarray(r))
            ref = ref[: len(read)]
            best = jnp.max(ref, axis=-1)
            tok = jnp.asarray(served, jnp.int32)
            gap = best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
            worst = max(worst, float(jnp.max(gap)))
            n_tok += len(served)
            if control:
                low = _fn(dims, True)(weights, jnp.asarray(toks), jnp.asarray(r))
                pick = jnp.argmax(low[: len(read)], axis=-1)
                g2 = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
                worst_ctl = max(worst_ctl, float(jnp.max(g2)))
    out = {"max_gap": worst, "tokens": n_tok}
    if control:
        out["control_max_gap"] = worst_ctl
    return out


def order_violations(jobs) -> int:
    """Stages that started before a parent stage had finished.

    Counts, over every finished job, each stage whose earliest task
    start precedes the latest task finish of one of its parents (on the
    cluster's clock).
    """
    bad = 0
    for gj in jobs:
        job = gj.job
        if job.finish_time < 0:
            continue
        for name, st in job.stages.items():
            starts = [t.start_time for t in st.tasks if t.start_time >= 0]
            if not starts:
                continue
            for p in job.parents_of(name):
                ps = job.stages.get(p)
                if ps is None:
                    continue
                fins = [t.finish_time for t in ps.tasks]
                if ps.will_execute and any(f < 0 for f in fins):
                    bad += 1
                elif fins and min(starts) < max(fins):
                    bad += 1
    return bad
