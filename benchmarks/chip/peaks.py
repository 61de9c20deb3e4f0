"""Chip peaks, and the operations and bytes a step's work requires.

Peaks are keyed by JAX's ``device_kind``; a device that is not listed
is an error, never a default.  The counters take the work from the
shapes the harness records around each call: the batch rows, their
context lengths and the prefill chunks.  They count what the model's
mathematics needs, not what an implementation happens to do: the
decode kernel's work is the K/V of live tokens only, whatever the
kernel reads besides.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 16 GB of HBM per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}: add it to "
                       "benchmarks/chip/peaks.py with its source")
    return PEAKS[kind]


def _layer_params(cfg) -> int:
    """Weights every token multiplies in the layers: attention and MLP."""
    d, q, kv = cfg.d_model, cfg.n_heads * cfg.hd, cfg.n_kv_heads * cfg.hd
    return cfg.n_layers * (d * q + 2 * d * kv + q * d + 3 * d * cfg.d_ff)


def attention_work(cfg, contexts: Iterable[int], kv_bytes: int = 2) -> Tuple[float, float]:
    """FLOPs and K/V bytes of one decode token per row, all layers.

    ``contexts`` are the tokens each query attends to (its own
    included).  QK^T and PV each take 2 * heads * head_dim FLOPs per
    context token; the bytes are each live token's K and V, read once
    per layer for the whole group of query heads.
    """
    ctx = sum(int(c) for c in contexts)
    flops = 4.0 * cfg.n_heads * cfg.hd * ctx * cfg.n_layers
    bytes_ = 2.0 * cfg.n_kv_heads * cfg.hd * kv_bytes * ctx * cfg.n_layers
    return flops, bytes_


def decode_step_flops(cfg, contexts) -> float:
    """Model FLOPs of one decode step over the real rows (padding excluded)."""
    contexts = list(contexts)
    attn, _ = attention_work(cfg, contexts)
    head = cfg.d_model * cfg.vocab
    return 2.0 * (_layer_params(cfg) + head) * len(contexts) + attn


def prefill_chunk_flops(cfg, past: int, chunk: int) -> float:
    """Model FLOPs of one prefill chunk: ``chunk`` queries after ``past``.

    Only the prompt's last token needs the head; it counts once per
    chunk (the program computes it for every token of the chunk, which
    is not model work).
    """
    ctx = [past + i + 1 for i in range(chunk)]
    attn, _ = attention_work(cfg, ctx)
    return 2.0 * (_layer_params(cfg) * chunk + cfg.d_model * cfg.vocab) + attn
