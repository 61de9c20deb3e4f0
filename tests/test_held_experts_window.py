"""Sliding-window paged kernels, the held-expert layer and the afmoe block.

- both paged kernels with a ``window`` against their oracles, bf16 and
  int8 pages, in interpret mode: decode rows on both sides of the
  window's first page, and prefill chunks that straddle it; the
  flash and slot-decode entry points refuse a window on the kernel path;
- the grouped ``expert_ffn`` kernel against its oracle in interpret
  mode, held experts that receive no token included;
- the share-sum test: the partial outputs of every share of the smoke
  config's experts, the shared expert counted once, add up to the uncut
  layer's output;
- ``PagedLLMEngine`` prefill, then decode through the cache past the
  window, against the full-sequence ``forward`` on seeded weights.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.expert_ffn import ROW_TILE, expert_ffn
from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_prefill_attention,
)
from repro.models import forward, init_params
from repro.models import moe as M
from repro.serving.engine import Request
from repro.serving.paged_engine import PagedLLMEngine

RNG = np.random.default_rng(7)
TOL = {jnp.float32: 3e-5, jnp.bfloat16: 2e-2}
Q_TOL = 3e-5


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32), dtype)


def _stack(pages, layer=1, n_layers=2):
    """A stored pool ``(L, P, ps, K·hd)`` with ``pages`` at ``layer``, noise elsewhere."""
    other = _rand(pages.shape).astype(pages.dtype)
    return jnp.stack([R.stored_pages(pages if i == layer else other)
                      for i in range(n_layers)])


def _stack_scales(scales, layer=1, n_layers=2):
    other = jnp.full(scales.shape, 7.0, jnp.float32)
    return jnp.stack([R.stored_scales(scales if i == layer else other)
                      for i in range(n_layers)])


def _pools(P, ps, K, hd, dtype, quantized):
    kp, vp = _rand((P, ps, K, hd), dtype), _rand((P, ps, K, hd), dtype)
    if not quantized:
        return (_stack(kp), _stack(vp)), {}
    kq, ks = R.quantize_kv_ref(kp.astype(jnp.float32))
    vq, vs = R.quantize_kv_ref(vp.astype(jnp.float32))
    return (_stack(kq), _stack(vq)), dict(k_scales=_stack_scales(ks),
                                          v_scales=_stack_scales(vs))


# ---------------------------------------------------------------------------
# windowed paged kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("H,K,hd,ps,window", [
    (8, 2, 64, 8, 12),       # window not a page multiple
    (32, 4, 128, 16, 32),    # trinity's heads, window of two pages
])
def test_windowed_decode_matches_oracle(H, K, hd, ps, window, kv):
    npp = 8
    dtype = jnp.bfloat16 if kv == "bf16" else jnp.float32
    # rows inside the window, on its first page's edge, and past it
    lens = jnp.asarray([3, window, window + 1, window + ps - 1, npp * ps],
                       jnp.int32)
    B = lens.shape[0]
    P = B * npp + 1
    q = _rand((B, H, hd), dtype)
    pools, scales = _pools(P, ps, K, hd, dtype, kv == "int8")
    bt = jnp.asarray(RNG.permutation(np.arange(1, P))[: B * npp].reshape(B, npp),
                     jnp.int32)
    out = paged_decode_attention(q, *pools, bt, lens, 1, interpret=True,
                                 window=window, **scales)
    ref = R.paged_decode_attention_ref(q, *pools, bt, lens, 1, window=window,
                                       **scales)
    tol = Q_TOL if kv == "int8" else TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)
    # the window changes the rows that reach past it, and no other
    full = R.paged_decode_attention_ref(q, *pools, bt, lens, 1, **scales)
    moved = np.abs(np.asarray(full, np.float32)
                   - np.asarray(ref, np.float32)).max(axis=(1, 2))
    assert (moved[np.asarray(lens) <= window] == 0).all()
    assert (moved[np.asarray(lens) > window] > 0).all()


def test_windowed_decode_skips_pages_before_the_window():
    """Pages wholly before a row's window are neither computed nor able
    to leak: poisoning them leaves the output as it was."""
    H, K, hd, ps, window, npp = 4, 2, 64, 8, 10, 6
    lens = jnp.asarray([40, 25], jnp.int32)
    B, P = 2, 2 * npp + 1
    q = _rand((B, H, hd))
    kp, vp = np.asarray(_rand((P, ps, K, hd))), np.asarray(_rand((P, ps, K, hd)))
    bt = np.arange(1, P).reshape(B, npp)
    kp2, vp2 = kp.copy(), vp.copy()
    for b in range(B):
        first = (int(lens[b]) - window) // ps
        for j in range(first):
            kp2[bt[b, j]], vp2[bt[b, j]] = 99.0, -99.0

    def run(k, v):
        return paged_decode_attention(
            q, _stack(jnp.asarray(k)), _stack(jnp.asarray(v)),
            jnp.asarray(bt, jnp.int32), lens, 1, interpret=True, window=window)

    np.testing.assert_allclose(np.asarray(run(kp, vp)),
                               np.asarray(run(kp2, vp2)), atol=1e-6)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("H,K,hd,ps,window,past,C", [
    (8, 2, 64, 8, 12, 20, 8),     # chunk straddles the window's first page
    (8, 2, 64, 8, 12, 3, 6),      # window starts before position 0
    (32, 4, 128, 16, 32, 40, 16),  # trinity's heads, grid starts at page 0
    (4, 1, 128, 16, 16, 50, 20),  # grid starts at page 2
])
def test_windowed_prefill_matches_oracle(H, K, hd, ps, window, past, C, kv):
    dtype = jnp.bfloat16 if kv == "bf16" else jnp.float32
    ctx = past + C
    npp = -(-ctx // ps) + 1
    P = npp + 3
    q = _rand((C, H, hd), dtype)
    pools, scales = _pools(P, ps, K, hd, dtype, kv == "int8")
    bt = jnp.asarray(RNG.permutation(np.arange(1, P))[:npp], jnp.int32)
    out = paged_prefill_attention(q, *pools, bt, past, 1, interpret=True,
                                  window=window, **scales)
    ref = R.paged_prefill_attention_ref(q, *pools, bt, past, 1, window=window,
                                        **scales)
    tol = Q_TOL if kv == "int8" else TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)
    # the oracle is causal attention with the window's band, from scratch
    kv_pages = [R.page_view(p[1], K) for p in pools]
    if scales:
        kv_pages = [R.dequantize_pages_ref(p, R.scale_view(s[1], K))
                    for p, s in zip(kv_pages, scales.values())]
    keys, vals = (R.gather_pages(p, bt[None]) for p in kv_pages)
    qpos = past + np.arange(C)[:, None, None]
    tpos = np.arange(keys.shape[1])[None, None, :]
    logits = np.einsum("chd,thd->cht", np.asarray(q, np.float32) * hd ** -0.5,
                       np.repeat(np.asarray(keys[0], np.float32), H // K, axis=1))
    band = (tpos <= qpos) & (tpos > qpos - window)
    w = np.where(band, np.exp(logits - np.where(band, logits, -np.inf).max(-1,
                                                                         keepdims=True)), 0)
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("cht,thd->chd", w,
                     np.repeat(np.asarray(vals[0], np.float32), H // K, axis=1))
    np.testing.assert_allclose(np.asarray(ref, np.float32), want,
                               atol=max(tol, 1e-4), rtol=max(tol, 1e-4))


@pytest.mark.parametrize("name", ["attention", "decode_attention"])
def test_windowed_dense_attention_refuses_the_kernel(name):
    """The flash and slot-decode kernels have no window: asking for the
    kernel with one raises instead of running the oracle unnoticed."""
    q, k, v = _rand((1, 4, 2, 8)), _rand((1, 4, 2, 8)), _rand((1, 4, 2, 8))
    if name == "attention":
        call = lambda impl: ops.attention(q, k, v, window=2, impl=impl)
    else:
        call = lambda impl: ops.decode_attention(
            q[:, 0], k, v, jnp.array([3]), window=2, impl=impl)
    with pytest.raises(NotImplementedError, match="no sliding window"):
        call("pallas")
    assert np.isfinite(np.asarray(call("ref"))).all()


# ---------------------------------------------------------------------------
# the grouped expert kernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("counts", [
    [3, 0, 17, 1],        # an empty expert, and one of two tiles
    [0, 0, 0, 5],         # leading empty experts
    [16, 4, 0, 0],        # trailing empty experts, an exactly full tile
])
def test_expert_ffn_matches_oracle(counts):
    d, f = 128, 256
    E = len(counts)
    counts = np.asarray(counts)
    tiles = -(-counts // ROW_TILE)
    start = np.cumsum(tiles) - tiles
    max_tiles = max(1, int(tiles.max()))
    M = (int(tiles.sum()) + 2) * ROW_TILE        # spare tiles past the groups
    x = _rand((M, d), jnp.bfloat16)
    wg, wu = _rand((E, d, f), jnp.bfloat16) * 0.1, _rand((E, d, f), jnp.bfloat16) * 0.1
    wd = _rand((E, f, d), jnp.bfloat16) * 0.1
    args = (x, wg, wu, wd, jnp.asarray(start, jnp.int32),
            jnp.asarray(tiles, jnp.int32))
    # stacked over three layers, the kernel reads layer 1's in place
    stacked = [jnp.stack([_rand(w.shape, w.dtype), w, _rand(w.shape, w.dtype)])
               for w in (wg, wu, wd)]
    out = np.asarray(expert_ffn(x, *stacked, *args[4:], max_tiles, 1,
                                interpret=True), np.float32)
    ref = np.asarray(R.expert_ffn_ref(*args, ROW_TILE), np.float32)
    for e in range(E):
        rows = slice(start[e] * ROW_TILE, start[e] * ROW_TILE + counts[e])
        np.testing.assert_allclose(out[rows], ref[rows], atol=2e-2, rtol=2e-2)
        # each expert's rows are its own SwiGLU
        own = R.swiglu_ref(x[rows], wg[e], wu[e], wd[e])
        np.testing.assert_allclose(ref[rows], np.asarray(own, np.float32),
                                   atol=1e-6)


def _smoke(dtype="float32", **moe):
    cfg = get_smoke_config("trinity-mini").with_(dtype=dtype)
    return cfg.with_(moe=dataclasses.replace(cfg.moe, **moe))


def _layer_params(cfg, key=0):
    mk_cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, n_held=0))
    params = init_params(mk_cfg, jax.random.key(key))[0]
    return jax.tree.map(lambda a: a[0], params["blocks"]["0"]["moe"])


def _share(p, first, held):
    return {**p, **{n: p[n][first:first + held]
                    for n in ("w_gate", "w_up", "w_down")}}


def test_held_layer_matches_one_hot_oracle():
    """The grouped path equals the one-hot layer restricted to the same
    held experts, and its counts are the held assignments."""
    cfg = _smoke()
    p = _share(_layer_params(cfg), 4, 4)
    cfg = _smoke(first_held=4, n_held=4)
    x = _rand((3, 5, cfg.d_model))
    valid = jnp.asarray([[True] * 5, [True] * 5, [True, True, False, False, True]])
    y, counts = M.moe_held(p, cfg, x, valid)
    ok = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(y)[ok],
                               np.asarray(M.moe_dense(p, cfg, x))[ok],
                               atol=1e-5, rtol=1e-5)
    # a padding row routes nothing: it gets the shared expert alone
    shared = M.mlp(p["shared"], x)
    np.testing.assert_allclose(np.asarray(y)[~ok], np.asarray(shared)[~ok],
                               atol=1e-5, rtol=1e-5)
    w, ids = M._routing(p, cfg, x.reshape(-1, cfg.d_model))
    ids = np.asarray(ids)[np.asarray(valid).reshape(-1)]
    want = [(ids == e).sum() for e in range(4, 8)]
    assert counts.tolist() == want + [ids.size - sum(want)]


def test_expert_shares_sum_to_the_uncut_layer():
    """Every share of the experts, with the shared expert counted once,
    adds up to the layer that holds them all."""
    cfg = _smoke()
    p = _layer_params(cfg)
    E, held = cfg.moe.n_experts, cfg.moe.n_held
    x = _rand((2, 6, cfg.d_model))
    whole = M.moe_held(p, _smoke(n_held=0), x)[0]
    shared = M.mlp(p["shared"], x.reshape(-1, cfg.d_model)).reshape(x.shape)
    total = shared
    for first in range(0, E, held):
        part, _ = M.moe_held(_share(p, first, held),
                             _smoke(first_held=first, n_held=held), x)
        total = total + (part - shared)
    # float32 sums in another order: outputs reach ~60, so 1e-4 is a few
    # units in the last place, far under one expert's share
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-4, rtol=1e-5)
    # and the uncut layer is the one-hot oracle over all experts
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(M.moe_dense(p, _smoke(n_held=0), x)),
        atol=1e-5, rtol=1e-5)


def test_held_layer_through_the_kernel_in_interpret_mode(monkeypatch):
    """The served path's kernel, interpreted, equals the oracle path."""
    cfg = _smoke()
    p = _share(_layer_params(cfg), 0, 4)
    x = _rand((1, 9, cfg.d_model))
    want = M.moe_held(p, cfg, x)[0]

    def interpreted(*args, impl="auto"):
        return expert_ffn(*args, interpret=True)

    monkeypatch.setattr(ops, "expert_ffn", interpreted)
    got = M.moe_held(p, cfg, x)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the paged engine past the window
# ---------------------------------------------------------------------------
def test_paged_engine_past_the_window_matches_forward():
    """Prefill, then decode through the paged cache well past the sliding
    window: every step's logits equal the full-sequence forward's."""
    cfg = get_smoke_config("trinity-mini").with_(dtype="float32")
    params = init_params(cfg, jax.random.key(3))[0]
    eng = PagedLLMEngine(cfg, max_seqs=2, max_len=64, page_size=4,
                         params=params, prefill_chunk=8)
    seen = []
    decode = eng._decode

    def recording(p, pools, toks, bt, lens):
        logits, pools = decode(p, pools, toks, bt, lens)
        seen.append(np.asarray(logits)[0])
        return logits, pools

    eng._decode = recording
    prompt = [int(t) for t in np.arange(13) * 5 % cfg.vocab]   # > window (8)
    req = Request(rid=0, prompt=prompt, max_new_tokens=16)
    assert eng.admit(req)
    while not req.done():
        eng.step()
    seq = prompt + req.out_tokens
    full = np.asarray(forward(params, cfg, jnp.asarray([seq]))[0][0])
    assert req.out_tokens[0] == int(np.argmax(full[len(prompt) - 1]))
    n = len(seen)
    assert n == len(req.out_tokens) - 1 and len(seq) - 1 > 2 * cfg.window
    np.testing.assert_allclose(np.stack(seen), full[len(prompt):len(prompt) + n],
                               atol=2e-4, rtol=2e-4)
    counts = eng.expert_counts()
    moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    assert counts.sum() == (len(prompt) + n) * cfg.moe.top_k * moe_layers
