"""Mixture-of-Experts: dense one-hot oracle, held-expert layer, and the
expert-parallel production path.

A device may hold a share of the experts (``moe.n_held`` of the router's
``n_experts``, from ``moe.first_held``): the router still scores all of
them, and assignments to experts held elsewhere add nothing here — that
partial result is what goes on to the next layer.

Three implementations (config ``moe.impl``):

- ``dense``: every held expert runs on every token, combined with top-k
  gate weights.  O(E) compute — smoke tests / correctness oracle only.

- ``held``: one device's share without a mesh.  The assignments to held
  experts are grouped by expert, each group starting on a row tile, and
  one grouped SwiGLU kernel (``kernels/expert_ffn.py``) runs each held
  expert over its own rows only, reading its weights once; the outputs
  are combined with the gate weights.  No capacity, no token dropped.

- ``ep``: production path under shard_map.
    * tokens  : sharded over (pod, data), replicated over ``model``;
    * experts : expert dim sharded over ``data``  (expert parallelism),
                expert-FFN dim sharded over ``model`` (tensor parallelism);
    * dataflow: route top-k locally → sort assignments by destination data
      shard → fixed-capacity all_to_all over ``data`` → local grouped GEMM
      (``jax.lax.ragged_dot``) on each shard's experts → all_to_all back →
      gate-weighted segment_sum → psum over ``model`` (FFN partials).
  Assignments beyond per-destination capacity (capacity_factor) are
  dropped — standard capacity semantics; gate weights renormalize.

Shared experts (DeepSeek/Kimi) always run densely (they see every token).
This layout fits 1T-param MoEs at 256 chips: kimi-k2 expert weights =
2.06 TB bf16 / (16 data × 16 model) ≈ 8 GB/chip.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from ..kernels.expert_ffn import ROW_TILE
from ..kernels.ref import held_experts_ref
from .config import ModelConfig
from .layers import Maker, Params, mlp


def init_moe(mk: Maker, cfg: ModelConfig) -> None:
    m = cfg.moe
    d = cfg.d_model
    mk.dense("router", (d, m.n_experts), ("embed", "experts"))
    # expert dim -> data (EP), ffn dim -> model (TP); a device holding a
    # share of the experts has only theirs
    mk.dense("w_gate", (m.held, d, m.d_ff_expert), ("experts_ep", None, "ff"))
    mk.dense("w_up", (m.held, d, m.d_ff_expert), ("experts_ep", None, "ff"))
    mk.dense("w_down", (m.held, m.d_ff_expert, d), ("experts_ep", "ff", None))
    if m.n_shared > 0:
        sh = mk.sub("shared")
        sh.dense("w_gate", (d, m.n_shared * m.d_ff_expert), ("embed", "ff"))
        sh.dense("w_up", (d, m.n_shared * m.d_ff_expert), ("embed", "ff"))
        sh.dense("w_down", (m.n_shared * m.d_ff_expert, d), ("ff", "embed"))
    if m.score_func == "sigmoid":
        mk.dense("router_bias", (m.n_experts,), ("experts",), fan_in=d)


def _routing(p: Params, cfg: ModelConfig, x2d: jax.Array):
    """Top-k experts of each token, of all the router's, and their weights.

    ``softmax``: the top-k probabilities, renormalised.  ``sigmoid``:
    float32 scores ``s = sigmoid(x @ router)``; the selection takes the
    top-k of ``s + router_bias`` (the bias acts on selection only) and
    the weights are the chosen ``s`` over their sum.  Both times
    ``route_scale``.
    """
    m = cfg.moe
    if m.score_func == "sigmoid":
        s = jax.nn.sigmoid(
            jnp.dot(x2d, p["router"], preferred_element_type=jnp.float32))
        _, ids = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), m.top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    else:
        logits = (x2d @ p["router"]).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)
    if m.route_scale != 1.0:
        w = w * m.route_scale
    return w, ids


def moe_dense(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Oracle: run every held expert on all tokens (tiny configs only)."""
    m = cfg.moe
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    w, ids = _routing(p, cfg, x2d)
    y = held_experts_ref(x2d, ids, w, p["w_gate"], p["w_up"], p["w_down"],
                         first=m.first_held).astype(x.dtype)
    if m.n_shared > 0:
        y = y + mlp(p["shared"], x2d)
    return y.reshape(B, S, d)


def moe_held(
    p: Params, cfg: ModelConfig, x: jax.Array,
    valid: Optional[jax.Array] = None, layer=None,
) -> Tuple[jax.Array, jax.Array]:
    """A device's share of the expert layer, through the grouped kernel.

    Routes each token over all ``n_experts``, keeps the assignments to
    the held experts (of tokens where ``valid``, shape ``(B, S)``, is
    true), places each held expert's rows in whole row tiles of its own,
    runs :func:`~repro.kernels.ops.expert_ffn` and combines with the
    gate weights; the shared experts are added for every token.  Expert
    weights stacked over layers (``(L, E_h, ...)``) are read in place at
    ``layer`` by the kernel; a single layer's are ``(E_h, ...)``.

    Returns ``(y, counts)``: ``counts`` is int32 ``(held + 1,)``, the
    valid tokens' assignments to each held expert, then to the experts
    held elsewhere.
    """
    m = cfg.moe
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    T, k = x2d.shape[0], m.top_k
    experts = [p[n] for n in ("w_gate", "w_up", "w_down")]
    if experts[0].ndim == 3:
        experts, layer = [e[None] for e in experts], 0
    E_h = experts[0].shape[1]
    w, ids = _routing(p, cfg, x2d)                            # (T, k)
    local = ids - m.first_held
    held = (local >= 0) & (local < E_h)
    ok = jnp.ones((T,), bool) if valid is None else valid.reshape(T)
    held &= ok[:, None]
    onehot = (local.reshape(-1)[:, None] == jnp.arange(E_h)[None, :]) \
        & held.reshape(-1)[:, None]                           # (T·k, E_h)
    counts = onehot.sum(0).astype(jnp.int32)
    tiles = (counts + ROW_TILE - 1) // ROW_TILE
    tile_start = jnp.cumsum(tiles) - tiles
    # static bounds: an expert takes a token at most once, and all the
    # groups' padding is under one tile each
    max_tiles = -(-T // ROW_TILE)
    n_tiles = min(E_h * max_tiles,
                  -(-(T * min(k, E_h) + E_h * (ROW_TILE - 1)) // ROW_TILE))
    M = n_tiles * ROW_TILE
    # row of each held assignment: its expert's first row + its rank there
    rank = jnp.cumsum(onehot, axis=0) - 1
    col = jnp.clip(local.reshape(-1), 0, E_h - 1)
    rank = jnp.take_along_axis(rank, col[:, None], axis=1)[:, 0]
    pos = jnp.where(held.reshape(-1), tile_start[col] * ROW_TILE + rank, M)
    src = jnp.full((M,), T, jnp.int32).at[pos].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), k), mode="drop")
    xs = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)])[src]
    ys = ops.expert_ffn(xs, *experts, tile_start, tiles, max_tiles, layer)
    ys = jnp.concatenate([ys, jnp.zeros((1, d), ys.dtype)])[pos]
    gate = (w * held).reshape(-1, 1)
    y = (ys.astype(jnp.float32) * gate).reshape(T, k, d).sum(1)
    if m.n_shared > 0:
        y = y + mlp(p["shared"], x2d).astype(jnp.float32)
    absent = ok.sum(dtype=jnp.int32) * k - counts.sum()
    return y.astype(x.dtype).reshape(B, S, d), jnp.append(counts, absent)


# ---------------------------------------------------------------------------
# Expert-parallel path
# ---------------------------------------------------------------------------
def _ep_body_dedup(
    x_local: jax.Array,            # (T_l, d)
    router: jax.Array,             # (d, E)
    w_gate: jax.Array,             # (E_l, d, f_l)
    w_up: jax.Array,
    w_down: jax.Array,
    cfg: ModelConfig,
    ep_axis: str,
    ep_size: int,
    tp_axis: Optional[str],
) -> jax.Array:
    """Deduplicated dispatch: one row per (token, destination shard).

    Top-k routing sends each token row up to k times; here a token's row
    crosses the wire once per *shard* owning ≥1 of its experts, with the
    (local expert id, gate weight) list piggybacked (tens of bytes vs a
    14 KB row).  With ``shard_groups`` (DeepSeek node-limited routing
    analogue) the destination count is capped, bounding a2a volume at
    L/k of the naive dispatch.  Receivers expand pairs back to
    assignments locally (HBM, not wire) for the grouped GEMM.
    """
    m = cfg.moe
    T_l, d = x_local.shape
    E = m.n_experts
    # static axis size (capacity math needs a Python int; jax.lax has no
    # axis_size and psum(1, axis) traces under shard_map)
    dsize = ep_size
    E_l = w_gate.shape[0]
    k = m.top_k

    logits = (x_local @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if m.shard_groups and m.shard_groups < dsize:
        # group-limited routing: keep only the top-L shards by mass
        shard_mass = probs.reshape(T_l, dsize, E_l).sum(-1)      # (T_l, ds)
        _, top_shards = jax.lax.top_k(shard_mass, m.shard_groups)
        allowed = jnp.zeros((T_l, dsize), bool).at[
            jnp.arange(T_l)[:, None], top_shards
        ].set(True)
        probs = jnp.where(
            jnp.repeat(allowed, E_l, axis=1), probs, 0.0
        )
        max_dest = m.shard_groups
    else:
        max_dest = min(k, dsize)
    w, ids = jax.lax.top_k(probs, k)
    w = (w / jnp.clip(w.sum(-1, keepdims=True), 1e-9)).astype(jnp.float32)
    dest = ids // E_l                                            # (T_l, k)
    leid = ids - dest * E_l

    # dense (token, shard) pair table — vectorized, no scatter
    shard_iota = jnp.arange(dsize)[None, :, None]                # (1, ds, 1)
    hit = dest[:, None, :] == shard_iota                         # (T_l, ds, k)
    pair_eid = jnp.where(hit, leid[:, None, :], E_l).astype(jnp.int32)
    pair_w = jnp.where(hit, w[:, None, :], 0.0).astype(jnp.float32)
    pair_exists = hit.any(-1)                                    # (T_l, ds)

    # fixed-capacity packing of pairs per destination
    A = T_l * dsize
    flat_dest = jnp.tile(jnp.arange(dsize)[None], (T_l, 1)).reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T_l), dsize)
    sort_key = jnp.where(pair_exists.reshape(-1), flat_dest, dsize)
    cap = max(8, int(T_l * max_dest * m.capacity_factor) // max(1, dsize))
    order = jnp.argsort(sort_key, stable=True)
    s_dest = sort_key[order]
    starts = jnp.searchsorted(s_dest, jnp.arange(dsize))
    rank = jnp.arange(A) - starts[s_dest]
    keep = (rank < cap) & (s_dest < dsize)
    slot = jnp.where(keep, s_dest * cap + rank, dsize * cap)

    R = dsize * cap
    send_rows = jnp.zeros((R + 1, d), x_local.dtype).at[slot].set(
        x_local[flat_tok[order]]
    )
    send_eid = jnp.full((R + 1, k), E_l, jnp.int32).at[slot].set(
        pair_eid.reshape(A, k)[order]
    )
    send_w = jnp.zeros((R + 1, k), jnp.float32).at[slot].set(
        pair_w.reshape(A, k)[order]
    )

    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=ep_axis, split_axis=0, concat_axis=0,
        tiled=True,
    )
    if m.dispatch_dtype == "int8":
        amax = jnp.max(jnp.abs(send_rows[:-1].astype(jnp.float32)), axis=-1)
        scl = jnp.maximum(amax, 1e-8) / 127.0
        q8 = jnp.clip(
            jnp.round(send_rows[:-1].astype(jnp.float32) / scl[:, None]),
            -127, 127,
        ).astype(jnp.int8)
        recv_rows = (
            a2a(q8).astype(jnp.float32) * a2a(scl[:, None])
        ).astype(x_local.dtype)
    else:
        recv_rows = a2a(send_rows[:-1])
    recv_eid = a2a(send_eid[:-1])
    recv_w = a2a(send_w[:-1])

    # --- receiver: expand pairs -> assignments (local HBM, not wire) ------
    C2 = max(8, int(T_l * k * m.capacity_factor) // max(1, dsize) * dsize)
    C2 = min(C2, R * k)
    a_eid = recv_eid.reshape(-1)                                  # (R*k,)
    a_pair = jnp.repeat(jnp.arange(R), k)
    a_w = recv_w.reshape(-1)
    g_order = jnp.argsort(jnp.where(a_eid < E_l, a_eid, E_l), stable=True)
    g_order = g_order[:C2]
    rows = recv_rows[a_pair[g_order]]                             # (C2, d)
    sel_eid = a_eid[g_order]
    counts = jnp.bincount(jnp.clip(sel_eid, 0, E_l), length=E_l + 1)[:E_l]
    h = jax.lax.ragged_dot(rows, w_gate, group_sizes=counts)
    u = jax.lax.ragged_dot(rows, w_up, group_sizes=counts)
    act = (jax.nn.silu(h.astype(jnp.float32)) * u.astype(jnp.float32)).astype(rows.dtype)
    yr = jax.lax.ragged_dot(act, w_down, group_sizes=counts)      # (C2, d)
    valid = sel_eid < E_l
    contrib = yr.astype(jnp.float32) * (a_w[g_order] * valid)[:, None]
    y_pairs = jax.ops.segment_sum(contrib, a_pair[g_order], num_segments=R)

    # --- return + combine ---------------------------------------------------
    comb_dt = jnp.float32 if m.combine_dtype == "float32" else jnp.bfloat16
    back = a2a(y_pairs.astype(comb_dt))                           # (R, d)
    back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)], axis=0)
    y = jax.ops.segment_sum(
        back[slot].astype(jnp.float32), flat_tok[order], num_segments=T_l
    )
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    return y.astype(x_local.dtype)


def _ep_body(
    x_local: jax.Array,            # (T_l, d) tokens of this (pod, data) shard
    router: jax.Array,             # (d, E) replicated
    w_gate: jax.Array,             # (E_l, d, f_l)
    w_up: jax.Array,               # (E_l, d, f_l)
    w_down: jax.Array,             # (E_l, f_l, d)
    cfg: ModelConfig,
    ep_axis: str,
    ep_size: int,
    tp_axis: Optional[str],
) -> jax.Array:
    m = cfg.moe
    T_l, d = x_local.shape
    E = m.n_experts
    didx = jax.lax.axis_index(ep_axis)
    dsize = ep_size  # static: capacity/slot shapes below must be Python ints
    E_l = w_gate.shape[0]
    A = T_l * m.top_k                                   # assignments

    w, ids = _routing({"router": router}, cfg, x_local)  # (T_l, k)
    flat_eid = ids.reshape(-1)
    flat_w = w.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T_l), m.top_k)
    dest = flat_eid // E_l                               # owning data shard

    # --- pack into fixed-capacity per-destination slots -------------------
    cap = max(8, int(A * m.capacity_factor) // max(1, dsize))
    order = jnp.argsort(dest, stable=True)               # group by dest
    s_dest = dest[order]
    # rank within destination group
    starts = jnp.searchsorted(s_dest, jnp.arange(dsize))
    rank = jnp.arange(A) - starts[s_dest]
    keep = rank < cap
    slot = jnp.where(keep, s_dest * cap + rank, dsize * cap)  # overflow slot

    send_rows = jnp.zeros((dsize * cap + 1, d), x_local.dtype)
    send_rows = send_rows.at[slot].set(x_local[flat_tok[order]])
    send_eid = jnp.full((dsize * cap + 1,), E * dsize, jnp.int32)
    send_eid = send_eid.at[slot].set(flat_eid[order].astype(jnp.int32))

    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=ep_axis, split_axis=0, concat_axis=0,
        tiled=True,
    )
    payload = send_rows[:-1]
    if m.dispatch_dtype == "int8":
        # quantized dispatch (DeepSeek-V3 fp8-dispatch analogue): rowwise
        # int8 payload + f32 scales — 2x fewer a2a wire bytes than bf16
        amax = jnp.max(jnp.abs(payload.astype(jnp.float32)), axis=-1)
        scl = jnp.maximum(amax, 1e-8) / 127.0
        q8 = jnp.clip(
            jnp.round(payload.astype(jnp.float32) / scl[:, None]), -127, 127
        ).astype(jnp.int8)
        recv_q = a2a(q8)
        recv_s = a2a(scl[:, None])[:, 0]
        recv_rows = (recv_q.astype(jnp.float32) * recv_s[:, None]).astype(
            x_local.dtype
        )
    else:
        recv_rows = a2a(payload)
    recv_eid = a2a(send_eid[:-1].reshape(dsize * cap, 1))[:, 0]

    # --- local grouped GEMM ------------------------------------------------
    leid = recv_eid - didx * E_l                          # local expert id
    valid = (leid >= 0) & (leid < E_l)
    leid = jnp.where(valid, leid, E_l)
    g_order = jnp.argsort(leid, stable=True)
    rows = recv_rows[g_order]
    counts = jnp.bincount(jnp.clip(leid, 0, E_l), length=E_l + 1)[:E_l]
    h = jax.lax.ragged_dot(rows, w_gate, group_sizes=counts)
    u = jax.lax.ragged_dot(rows, w_up, group_sizes=counts)
    act = (jax.nn.silu(h.astype(jnp.float32)) * u.astype(jnp.float32)).astype(rows.dtype)
    yr = jax.lax.ragged_dot(act, w_down, group_sizes=counts)  # (R, d) partial/f
    # unsort back to slot order; zero the invalid rows
    inv = jnp.zeros_like(g_order).at[g_order].set(jnp.arange(g_order.shape[0]))
    yr = yr[inv] * valid[:, None]

    # --- return to source shards + combine ---------------------------------
    back = a2a(yr)                                        # (dsize*cap, d)
    back = jnp.concatenate([back, jnp.zeros((1, d), back.dtype)], axis=0)
    contrib = back[slot] * (flat_w[order] * keep)[:, None].astype(back.dtype)
    comb_dt = jnp.float32 if m.combine_dtype == "float32" else jnp.bfloat16
    y = jax.ops.segment_sum(
        contrib.astype(comb_dt), flat_tok[order], num_segments=T_l
    )
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)                      # sum FFN partials
    return y.astype(x_local.dtype)


def moe_ep(
    p: Params,
    cfg: ModelConfig,
    x: jax.Array,
    mesh=None,
) -> jax.Array:
    """Expert-parallel MoE under shard_map; falls back to dense w/o mesh."""
    from ..distributed.sharding import active_mesh

    mesh = mesh or active_mesh()
    m = cfg.moe
    if mesh is None or "data" not in mesh.shape or m.n_experts % mesh.shape["data"]:
        return moe_dense(p, cfg, x)
    n_tok = x.shape[0] * x.shape[1]
    tok_shards = mesh.shape["data"] * mesh.shape.get("pod", 1)
    if n_tok % tok_shards or (n_tok // tok_shards) < m.top_k:
        # decode-style tiny token counts: dense dispatch is cheaper than
        # a degenerate all_to_all (and shard_map needs divisibility)
        return moe_dense(p, cfg, x)
    tp_axis = "model" if "model" in mesh.shape else None
    if tp_axis and m.d_ff_expert % mesh.shape[tp_axis]:
        tp_axis = None

    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    batch_axes: Tuple[str, ...] = tuple(
        a for a in ("pod", "data") if a in mesh.shape
    )

    body_fn = _ep_body_dedup if m.dedup_dispatch else _ep_body
    body = functools.partial(
        body_fn, cfg=cfg, ep_axis="data", ep_size=mesh.shape["data"],
        tp_axis=tp_axis,
    )
    y2d = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None),
            P(None, None),
            P("data", None, tp_axis),
            P("data", None, tp_axis),
            P("data", tp_axis, None),
        ),
        out_specs=P(batch_axes, None),
        check_vma=False,
    )(x2d, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared > 0:
        y2d = y2d + mlp(p["shared"], x2d)
    return y2d.reshape(B, S, d)


def moe_block(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.moe.impl == "ep":
        return moe_ep(p, cfg, x)
    return moe_dense(p, cfg, x)
