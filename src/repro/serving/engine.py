"""Continuous-batching LLM engine — one "LLM executor" of the paper.

A slot-based engine around the model zoo's prefill/decode steps:
- up to ``max_batch`` concurrent requests (slots);
- each step decodes one token for every active slot (iteration-level
  scheduling à la Orca — new requests join between steps via prefill);
- per-token latency is measured per batch size, feeding the
  batching-aware calibration profile (Eq. 2) back to the scheduler.

This is intentionally a *real* engine (jit'd JAX compute, real tokens) so
the testbed benchmark exercises the same scheduler code paths the paper's
vLLM testbed does — just with a tiny model so it runs on CPU.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.calibration import LatencyProfile, measured_profile
from ..models import decode_step, init_cache, init_params, prefill
from ..models.config import ModelConfig


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    stop_token: Optional[int] = None
    out_tokens: List[int] = field(default_factory=list)
    # host stamps, all on time.perf_counter(): the latest placement on
    # an engine's rows (first placement, re-admission after preemption,
    # import after migration), the first output token, the finish
    placed_at: float = -1.0
    started_at: float = -1.0
    finished_at: float = -1.0
    on_finish: Optional[Callable[["Request"], None]] = None
    # prompt tokens actually run through prefill (cumulative across
    # recompute restarts; prefix-cache hits skip tokens and so reduce it)
    prefill_tokens: int = 0
    # admission urgency: lower drains first from a paged engine's
    # waiting queue (SLO jobs carry their scaled deadline — EDF);
    # inf (default) keeps the historical FIFO order byte-for-byte
    priority: float = math.inf
    # fleet-global first-admission stamp (set by the first engine that
    # places the request; preserved across eviction and migration).
    # Equal-priority waiting requests drain in this order — the deque
    # itself reflects *eviction* order, and a migrated-in request
    # evicted late would otherwise jump ahead of an older waiter.
    arrival_seq: int = -1

    def done(self) -> bool:
        return self.finished_at >= 0


class LatencyProfileMixin:
    """Measured l(b) bookkeeping shared by the slot and paged engines.

    ``_lat_samples`` maps batch size -> per-step latencies; the profile is
    refit only when new measurements arrived, so the returned object's
    identity is stable between measurements and schedulers can key
    calibration caches on it.
    """

    _lat_samples: Dict[int, List[float]]
    _profile_memo: Optional[Tuple[Tuple[Tuple[int, int], ...], Optional[LatencyProfile]]]

    def _init_latency(self) -> None:
        self._lat_samples = {}
        self._profile_memo = None

    def record_latency(self, batch: int, dt: float) -> None:
        self._lat_samples.setdefault(batch, []).append(dt)

    def latency_profile(self) -> Optional[LatencyProfile]:
        """Measured l(b): per-token step latency per batch size (Eq. 2).
        The first sample per batch size is dropped (JIT warm-up)."""
        fp = tuple(sorted((b, len(v)) for b, v in self._lat_samples.items()))
        if self._profile_memo is not None and self._profile_memo[0] == fp:
            return self._profile_memo[1]
        samples = {
            b: (v[1:] if len(v) > 1 else v)
            for b, v in self._lat_samples.items()
            if v
        }
        prof = measured_profile(samples) if samples else None
        self._profile_memo = (fp, prof)
        return prof


class LLMEngine(LatencyProfileMixin):
    """One LLM executor with continuous batching over static slots."""

    preemptions = 0  # slot engines never evict (interface parity)

    def __init__(
        self,
        cfg: ModelConfig,
        max_batch: int = 8,
        max_len: int = 256,
        seed: int = 0,
        params: Optional[Any] = None,
        greedy: bool = True,
    ) -> None:
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.greedy = greedy
        key = jax.random.key(seed)
        self.params = params if params is not None else init_params(cfg, key)[0]
        # slot state
        self.cache = init_cache(cfg, max_batch, max_len)
        self.active: Dict[int, Request] = {}      # slot -> request
        self.free_slots = list(range(max_batch))
        self._tokens = np.zeros((max_batch,), np.int32)
        self._init_latency()

        self._decode = jax.jit(
            lambda p, c, t: decode_step(p, cfg, c, t)
        )

        # per-request single-slot prefill (slot caches merged afterwards)
        def _prefill_one(p, toks):
            return prefill(p, cfg, toks, max_len=max_len)

        self._prefill = jax.jit(_prefill_one)

    # -- admission ----------------------------------------------------------
    def can_admit(self) -> bool:
        return len(self.free_slots) > 0

    @property
    def batch_size(self) -> int:
        return len(self.active)

    def admit(self, req: Request) -> bool:
        """Prefill the request into a free slot."""
        if not self.free_slots:
            return False
        slot = self.free_slots.pop(0)
        toks = jnp.asarray([req.prompt], jnp.int32)
        req.prefill_tokens += len(req.prompt)
        last_logits, req_cache = self._prefill(self.params, toks)
        self._merge_slot(slot, req_cache, len(req.prompt))
        first = self._pick(last_logits[0])
        req.out_tokens.append(int(first))
        req.started_at = time.perf_counter()
        self._tokens[slot] = int(first)
        self.active[slot] = req
        return True

    def _pick(self, logits: jax.Array) -> int:
        return int(jnp.argmax(logits))

    def _merge_slot(self, slot: int, req_cache: Dict[str, Any], plen: int) -> None:
        """Copy a single-request prefill cache into the engine's slot."""

        def merge(dst, src):
            if not hasattr(dst, "shape"):
                return dst
            if dst.ndim == src.ndim and dst.shape[0] != src.shape[0] and src.shape[0] == 1:
                # batch-leading leaf (prefix caches)
                return dst.at[slot : slot + 1].set(src.astype(dst.dtype))
            if dst.ndim == src.ndim and dst.shape[1] != src.shape[1] and src.shape[1] == 1:
                # (sb, B, ...) stacked leaf
                return dst.at[:, slot : slot + 1].set(src.astype(dst.dtype))
            if dst.shape == src.shape:
                return src
            return dst

        def walk(dst, src):
            if isinstance(dst, dict):
                return {k: walk(dst[k], src[k]) for k in dst}
            if isinstance(dst, (tuple, list)):
                return type(dst)(walk(a, b) for a, b in zip(dst, src))
            return merge(dst, src)

        # batch-dim detection by position: cache leaves are (B, ...) for
        # prefix/lengths and (sb, B, ...) for scanned blocks
        def merge_by_path(path, dst, src):
            if not hasattr(dst, "shape") or dst.shape == ():
                return dst
            names = [p.key for p in path if hasattr(p, "key")]
            leaf = names[-1] if names else ""
            if leaf == "lengths":
                return dst.at[slot].set(src[0])
            bdim = 1 if (names and names[0] == "blocks" and dst.ndim >= 2) else 0
            if leaf in ("c", "n", "m", "h", "C", "conv", "ssm") and names[0] == "blocks":
                bdim = 1
            idx = [slice(None)] * dst.ndim
            idx[bdim] = slice(slot, slot + 1)
            return dst.at[tuple(idx)].set(src.astype(dst.dtype))

        self.cache = jax.tree_util.tree_map_with_path(
            merge_by_path, self.cache, req_cache
        )

    # -- decode loop ----------------------------------------------------------
    def step(self) -> List[Request]:
        """One decode step for all active slots; returns finished requests."""
        if not self.active:
            return []
        b = self.batch_size
        t0 = time.perf_counter()
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(self._tokens)
        )
        logits = np.asarray(jax.device_get(logits))
        self.record_latency(b, time.perf_counter() - t0)

        finished = []
        for slot, req in list(self.active.items()):
            nxt = int(np.argmax(logits[slot]))
            req.out_tokens.append(nxt)
            self._tokens[slot] = nxt
            limit = (
                len(req.out_tokens) >= req.max_new_tokens
                or (req.stop_token is not None and nxt == req.stop_token)
                or int(np.asarray(self.cache["lengths"])[slot]) >= self.max_len - 2
            )
            if limit:
                req.finished_at = time.perf_counter()
                finished.append(req)
                del self.active[slot]
                self.free_slots.append(slot)
                if req.on_finish:
                    req.on_finish(req)
        return finished
