"""Paged attention kernels (decode + fused chunked prefill) vs oracles.

All Pallas calls run in interpret mode so the sweep works on CPU CI;
shapes sweep head counts (MHA/GQA/MQA), page sizes, ragged per-request
lengths, and dtypes per the kernel-hardening contract.  The quantized
sweeps run both kernels over int8 pages with per-page scales and
compare against the dense oracle on the *dequantized* pools — the
quantization error itself is bounded separately (round-trip and
hypothesis property tests on ``quantize_kv_ref``).
"""

import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_prefill_attention,
    write_scales,
)

RNG = np.random.default_rng(42)

TOL = {jnp.float32: 3e-5, jnp.bfloat16: 2e-2}
# int8 paths: dominated by quantization, not kernel arithmetic
Q_TOL = 3e-5


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32), dtype)


def _stack(pages, layer=1, n_layers=2):
    """Stored pool ``(L, P, ps, K·hd)`` holding ``pages`` at ``layer``.

    The other layers hold noise, so a kernel that reads the wrong
    layer fails the comparison.
    """
    pages = jnp.asarray(pages)
    other = _rand(pages.shape, jnp.float32).astype(pages.dtype)
    layers = [pages if i == layer else other for i in range(n_layers)]
    return jnp.stack([R.stored_pages(x) for x in layers])


def _stack_scales(scales, layer=1, n_layers=2):
    """Stored scale pool ``(L, P, 1, ps·K)`` holding ``scales`` at ``layer``."""
    other = jnp.full(scales.shape, 7.0, jnp.float32)
    layers = [scales if i == layer else other for i in range(n_layers)]
    return jnp.stack([R.stored_scales(x) for x in layers])


def _random_tables(B, npp, P):
    """Permuted, non-contiguous page assignments (page 0 reserved)."""
    perm = RNG.permutation(np.arange(1, P))[: B * npp].reshape(B, npp)
    return jnp.asarray(perm, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,K,hd,ps,npp",
    [
        (2, 8, 2, 64, 16, 8),     # GQA g=4
        (1, 4, 4, 64, 8, 16),     # MHA, small pages
        (3, 4, 1, 128, 32, 4),    # MQA, wide heads, big pages
        (2, 16, 8, 64, 16, 6),    # many kv heads
        (4, 6, 2, 64, 8, 5),      # odd head-group/page combo
        (2, 32, 32, 64, 16, 3),   # stablelm-1.6b: MHA 32 x 64
        (2, 48, 8, 128, 16, 3),   # internlm2-20b: GQA 48 over 8 x 128
    ],
)
def test_paged_decode_matches_oracles(B, H, K, hd, ps, npp, dtype):
    P = B * npp + 1
    q = _rand((B, H, hd), dtype)
    kp = _rand((P, ps, K, hd), dtype)
    vp = _rand((P, ps, K, hd), dtype)
    bt = _random_tables(B, npp, P)
    lens = jnp.asarray(RNG.integers(1, npp * ps + 1, size=(B,)), jnp.int32)
    ks, vs = _stack(kp), _stack(vp)
    out = paged_decode_attention(q, ks, vs, bt, lens, 1, interpret=True)
    ref = R.paged_decode_attention_ref(q, ks, vs, bt, lens, 1)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )
    # oracle self-consistency: paged ref == dense ref on the gathered view
    dense = R.decode_attention_ref(
        q, R.gather_pages(kp, bt), R.gather_pages(vp, bt), lens
    )
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(dense, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


def test_paged_decode_ignores_garbage_pages():
    """Unreferenced pages and the region past `lengths` must not leak
    into the output — freed-page recycling depends on this."""
    B, H, K, hd, ps, npp = 3, 4, 2, 64, 8, 6
    P = B * npp + 3
    q = _rand((B, H, hd), jnp.float32)
    kp = np.asarray(_rand((P, ps, K, hd), jnp.float32))
    vp = np.asarray(_rand((P, ps, K, hd), jnp.float32))
    bt = np.asarray(_random_tables(B, npp, P))
    lens = np.asarray(RNG.integers(1, npp * ps, size=(B,)), np.int64)

    kp2, vp2 = kp.copy(), vp.copy()
    referenced = set(bt.reshape(-1).tolist())
    for p in range(P):
        if p not in referenced:  # poison unreferenced pages
            kp2[p] = 99.0
            vp2[p] = -99.0
    for b in range(B):  # poison the tail past each request's length
        for j in range(npp):
            lo = max(0, int(lens[b]) - j * ps)
            if lo < ps:
                kp2[bt[b, j], lo:] = 77.0
                vp2[bt[b, j], lo:] = -77.0

    args = (jnp.asarray(bt, jnp.int32), jnp.asarray(lens, jnp.int32), 1)
    o1 = paged_decode_attention(q, _stack(kp), _stack(vp), *args,
                                interpret=True)
    o2 = paged_decode_attention(q, _stack(kp2), _stack(vp2), *args,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


def test_ops_dispatch_paged_matches_ref():
    """The ops-layer entry point (ref impl on CPU) equals the oracle."""
    B, H, K, hd, ps, npp = 2, 4, 2, 64, 16, 4
    P = B * npp + 1
    q = _rand((B, H, hd), jnp.float32)
    kp = _rand((P, ps, K, hd), jnp.float32)
    vp = _rand((P, ps, K, hd), jnp.float32)
    bt = _random_tables(B, npp, P)
    lens = jnp.asarray([5, 37], jnp.int32)
    ks, vs = _stack(kp), _stack(vp)
    out = ops.paged_decode_attention(q, ks, vs, bt, lens, 1)
    ref = R.paged_decode_attention_ref(q, ks, vs, bt, lens, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# fused chunked prefill
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "H,K,hd,ps,past,C",
    [
        (8, 2, 64, 16, 0, 16),    # first chunk, page-aligned
        (4, 4, 64, 8, 8, 8),      # aligned continuation
        (4, 2, 64, 8, 12, 7),     # non-aligned past AND tail
        (4, 1, 128, 32, 5, 3),    # MQA, chunk inside one page
        (6, 2, 64, 8, 17, 23),    # odd everything, multi-page chunk
        (32, 32, 64, 16, 14, 20),  # stablelm-1.6b: MHA 32 x 64
        (48, 8, 128, 16, 14, 20),  # internlm2-20b: GQA 48 over 8 x 128
    ],
)
def test_paged_prefill_matches_oracles(H, K, hd, ps, past, C, dtype):
    ctx = past + C
    npp = -(-ctx // ps) + 2          # slack pages past the context
    P = npp + 4
    q = _rand((C, H, hd), dtype)
    kp = _rand((P, ps, K, hd), dtype)
    vp = _rand((P, ps, K, hd), dtype)
    bt = _random_tables(1, npp, P)[0]
    ks, vs = _stack(kp), _stack(vp)
    out = paged_prefill_attention(q, ks, vs, bt, past, 1, interpret=True)
    ref = R.paged_prefill_attention_ref(q, ks, vs, bt, past, 1)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )
    # oracle self-consistency: the paged ref IS the dense path (gather
    # + causal attention_ref with a query offset) — exactly what the
    # pre-fused prefill computed, so fp32 equality here certifies the
    # fused kernel against the historical dense implementation
    n_ctx = -(-ctx // ps)
    dense = R.attention_ref(
        q[None],
        R.gather_pages(kp, bt[None, :n_ctx]).reshape(1, -1, K, hd),
        R.gather_pages(vp, bt[None, :n_ctx]).reshape(1, -1, K, hd),
        causal=True, q_offset=past, kv_len=jnp.array([ctx], jnp.int32),
    )[0]
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(dense, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype],
    )


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_prefill_ignores_garbage(quantized):
    """Pages outside the block table and slots >= ctx must not leak —
    including under int8, where a garbage *scale* could amplify them."""
    H, K, hd, ps, past, C = 4, 2, 64, 8, 12, 7
    ctx = past + C
    npp = -(-ctx // ps)
    P = npp + 5
    q = _rand((C, H, hd), jnp.float32)
    kp = np.asarray(_rand((P, ps, K, hd), jnp.float32))
    vp = np.asarray(_rand((P, ps, K, hd), jnp.float32))
    bt = np.asarray(_random_tables(1, npp, P)[0])

    kp2, vp2 = kp.copy(), vp.copy()
    for p in range(P):
        if p not in set(bt.tolist()):
            kp2[p] = 99.0
            vp2[p] = -99.0
    tail = ctx - (npp - 1) * ps      # live slots in the last ctx page
    if tail < ps:
        kp2[bt[-1], tail:] = 77.0
        vp2[bt[-1], tail:] = -77.0

    def run(kparr, vparr):
        kj, vj = jnp.asarray(kparr), jnp.asarray(vparr)
        if quantized:
            kq, ks = R.quantize_kv_ref(kj)
            vq, vs = R.quantize_kv_ref(vj)
            return paged_prefill_attention(
                q, _stack(kq), _stack(vq), jnp.asarray(bt, jnp.int32), past,
                1, interpret=True, k_scales=_stack_scales(ks),
                v_scales=_stack_scales(vs),
            )
        return paged_prefill_attention(
            q, _stack(kj), _stack(vj), jnp.asarray(bt, jnp.int32), past, 1,
            interpret=True,
        )

    np.testing.assert_allclose(
        np.asarray(run(kp, vp)), np.asarray(run(kp2, vp2)), atol=1e-6
    )


# ---------------------------------------------------------------------------
# int8 pages with per-page scales
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "B,H,K,hd,ps,npp",
    [
        (2, 8, 2, 64, 16, 8),
        (3, 4, 1, 128, 32, 4),
        (4, 6, 2, 64, 8, 5),
        (2, 32, 32, 64, 16, 3),   # stablelm-1.6b
        (2, 48, 8, 128, 16, 3),   # internlm2-20b
    ],
)
def test_paged_decode_quantized_matches_oracle(B, H, K, hd, ps, npp):
    P = B * npp + 1
    q = _rand((B, H, hd), jnp.float32)
    kq, ks = R.quantize_kv_ref(_rand((P, ps, K, hd), jnp.float32))
    vq, vs = R.quantize_kv_ref(_rand((P, ps, K, hd), jnp.float32))
    bt = _random_tables(B, npp, P)
    lens = jnp.asarray(RNG.integers(1, npp * ps + 1, size=(B,)), jnp.int32)
    pools = (_stack(kq), _stack(vq))
    scales = dict(k_scales=_stack_scales(ks), v_scales=_stack_scales(vs))
    out = paged_decode_attention(
        q, *pools, bt, lens, 1, interpret=True, **scales
    )
    ref = R.paged_decode_attention_ref(q, *pools, bt, lens, 1, **scales)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=Q_TOL, rtol=Q_TOL
    )
    # and the ref equals dense attention over explicitly dequantized pools
    dense = R.decode_attention_ref(
        q,
        R.gather_pages(R.dequantize_pages_ref(kq, ks), bt),
        R.gather_pages(R.dequantize_pages_ref(vq, vs), bt),
        lens,
    )
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(dense), atol=Q_TOL, rtol=Q_TOL
    )


def _check_prefill_quantized(H, K, hd, ps, past, C):
    ctx = past + C
    npp = -(-ctx // ps) + 1
    P = npp + 3
    q = _rand((C, H, hd), jnp.float32)
    kq, ks = R.quantize_kv_ref(_rand((P, ps, K, hd), jnp.float32))
    vq, vs = R.quantize_kv_ref(_rand((P, ps, K, hd), jnp.float32))
    bt = _random_tables(1, npp, P)[0]
    pools = (_stack(kq), _stack(vq))
    scales = dict(k_scales=_stack_scales(ks), v_scales=_stack_scales(vs))
    out = paged_prefill_attention(
        q, *pools, bt, past, 1, interpret=True, **scales
    )
    ref = R.paged_prefill_attention_ref(q, *pools, bt, past, 1, **scales)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=Q_TOL, rtol=Q_TOL
    )


@pytest.mark.parametrize("past,C", [(0, 16), (12, 7), (17, 23)])
def test_paged_prefill_quantized_matches_oracle(past, C):
    _check_prefill_quantized(4, 2, 64, 8, past, C)


@pytest.mark.parametrize(
    "H,K,hd",
    [(32, 32, 64),    # stablelm-1.6b: MHA 32 x 64
     (48, 8, 128)],   # internlm2-20b: GQA 48 over 8 x 128
)
def test_paged_prefill_quantized_served_shapes(H, K, hd):
    _check_prefill_quantized(H, K, hd, 16, 14, 20)


@pytest.mark.parametrize(
    "pages,slots",
    [
        ([5, 3, 9], [0, 7, 2]),                  # a decode batch: one per page
        ([4, 4, 4, 6, 6], [5, 6, 7, 0, 1]),      # a prefill chunk over two pages
        ([2, 0, 0], [3, 0, 0]),                  # padding rows on the trash page
    ],
)
def test_write_scales_matches_scatter(pages, slots):
    """The in-place scale writer equals the oracle's scatter, and leaves
    every other slot, page and layer as it was."""
    L, P, ps, K = 2, 10, 8, 4
    pool = jnp.asarray(RNG.uniform(0.5, 2.0, size=(L, P, 1, ps * K)),
                       jnp.float32)
    vals = jnp.asarray(RNG.uniform(0.5, 2.0, size=(len(pages), K)),
                       jnp.float32)
    pages = jnp.asarray(pages, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    want = R.write_scales_ref(pool, 1, pages, slots, vals)
    got = write_scales(pool, 1, pages, slots, vals, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    view = np.asarray(R.scale_view(got, K))
    for t, (p, slot) in enumerate(zip(pages.tolist(), slots.tolist())):
        if p:   # the trash page's slot is written twice
            np.testing.assert_array_equal(view[1, p, slot], np.asarray(vals[t]))


def test_quantize_roundtrip_error_bound():
    """|dequant(quant(x)) - x| <= scale/2 elementwise, scales positive."""
    x = _rand((7, 8, 3, 32), jnp.float32)
    q, s = R.quantize_kv_ref(x)
    assert q.dtype == jnp.int8
    assert s.dtype == jnp.float32
    assert bool(jnp.all(s > 0))
    err = jnp.abs(R.dequantize_pages_ref(q, s) - x)
    assert bool(jnp.all(err <= s[..., None] * 0.5 + 1e-7))
    # zero vectors quantize to exact zeros with the floor scale
    q0, s0 = R.quantize_kv_ref(jnp.zeros((2, 4, 1, 8), jnp.float32))
    assert bool(jnp.all(q0 == 0)) and bool(jnp.all(s0 > 0))


@settings(max_examples=50, deadline=None)
@given(
    vals=st.lists(
        st.floats(min_value=-1e4, max_value=1e4, allow_nan=False,
                  width=32),
        min_size=8, max_size=8,
    )
)
def test_quantize_error_bound_property(vals):
    """Hypothesis: the per-vector scale bounds round-trip error at any
    magnitude (amax/127-scaled, so error <= scale/2 + float eps)."""
    x = jnp.asarray(np.array(vals, np.float32).reshape(1, 1, 1, 8))
    q, s = R.quantize_kv_ref(x)
    err = np.asarray(jnp.abs(R.dequantize_pages_ref(q, s) - x))
    bound = float(s.reshape(())) * 0.5 * (1 + 1e-5) + 1e-7
    assert err.max() <= bound
