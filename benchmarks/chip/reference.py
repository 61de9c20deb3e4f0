"""Plain float32 reference of the served model, and its fp8 control.

Imports nothing of the program.  It takes the weights the harness made
from the seed (in the layout the program reads), the prompts and the
served tokens, and runs the configuration's block module
(``blocks/<block>.py``, ``forward``): the full causal forward pass in
float32 at ``highest`` matmul precision, every linear layer through
:func:`_mm`.  The block reads its sizes from the configuration file.

Each sequence runs as one call over all layers, padded at its end to a
bucket length (causal attention never looks ahead, so the padding
changes nothing before it).  A block computes attention ``Q_BLOCK``
queries at a time, so that a long sequence fits beside the weights.

The control (``fp8``) is the same forward pass with every linear
layer's operands rounded to float8 e4m3 (per-tensor scale for weights,
per-row scale for activations), accumulated in float32: the step below
the bf16 the configurations state.
"""

from __future__ import annotations

import json
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


_JITS: Dict[Tuple, object] = {}


def _fn(block, spec, fp8: bool):
    """The block's forward pass, jitted once per block, sizes and precision."""
    import jax

    key = (block.__file__, json.dumps(spec, sort_keys=True), fp8)
    if key not in _JITS:
        _JITS[key] = jax.jit(partial(block.forward, spec=spec, fp8=fp8))
    return _JITS[key]


def bucket(n: int, cap: int) -> int:
    b = 256
    while b < n:
        b *= 2
    return min(b, cap)


def logit_gaps(weights, spec, block, seqs: Sequence[Tuple[List[int], List[int]]],
               cap: int, control: bool = False) -> Dict[str, object]:
    """Gaps of the served tokens below the reference's best logit.

    ``block`` is the configuration's block module (``harness.load_block``)
    and ``spec`` its configuration file.  ``seqs`` holds
    ``(prompt, served_tokens)`` pairs.  For each served
    token the gap is ``max(ref_logits) - ref_logits[token]`` at the
    position that predicts it; 0 where the reference agrees.  With
    ``control``, also the gap of the token the fp8 control puts first
    at each of those positions.

    Returns ``{"max_gap", "tokens", "control_max_gap"?}``.
    """
    import jax
    import jax.numpy as jnp

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
            ref = _fn(block, spec, False)(weights, jnp.asarray(toks), jnp.asarray(r))
            ref = ref[: len(read)]
            best = jnp.max(ref, axis=-1)
            tok = jnp.asarray(served, jnp.int32)
            gap = best - jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
            worst = max(worst, float(jnp.max(gap)))
            n_tok += len(served)
            if control:
                low = _fn(block, spec, True)(weights, jnp.asarray(toks), jnp.asarray(r))
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
