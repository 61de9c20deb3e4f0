"""Fig. 11 — fused paged-attention kernels and int8 KV pages.

Three sections, one JSON artifact (``benchmarks/out/fig11_kernels.json``):

- ``kernel_error`` — max |kernel − oracle| for the paged decode and the
  fused chunked-prefill kernels, fp32 and int8 pages, on seeded random
  pools.  The fp32 numbers certify the fused path against the dense
  reference; the int8 numbers bound the quantization error the per-page
  scales admit.
- ``capacity`` — pages (and tokens) a fixed byte budget buys under each
  ``kv_dtype``: the static ~1.6–4× capacity-per-byte claim (exact ratio
  depends on the compute dtype; int8 pays 4 bytes of scale per token
  per kv head on top of the 1-byte payload).
- ``serving`` — the claim end to end: two ``PagedLLMEngine`` fleets at
  an *equal KV byte budget* (``pages_for_byte_budget``), fp32 vs int8
  pages, serving the same seeded burst step-deterministically.  int8
  must admit strictly more concurrent requests and must not regress
  average JCT by more than 5 % (it should *improve* it at a starved
  budget — fewer evictions); both gates are recorded in the artifact
  for the nightly workflow to enforce.

A ``roofline`` block accompanies the capacity section: per decoded
token, attention reads the whole resident KV once, so bytes-per-token
drop by the same ratio pages grow — the kernel stays memory-bound and
the capacity win is also a bandwidth win.

CLI::

    PYTHONPATH=src python -m benchmarks.fig11_kernels           # full
    PYTHONPATH=src python -m benchmarks.fig11_kernels --quick
"""

from __future__ import annotations

import json
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_prefill_attention,
)
from repro.kernels.ref import (
    attention_ref,
    dequantize_pages_ref,
    gather_pages,
    quantize_kv_ref,
)
from repro.models import init_params
from repro.serving import PagedLLMEngine, Request

from .common import emit_csv

OUT_DIR = Path(__file__).parent / "out"


# ---------------------------------------------------------------------------
# section 1: kernel error vs oracle
# ---------------------------------------------------------------------------
def _rand_pools(key, n_pages, page_size, K, hd):
    kk, kv = jax.random.split(key)
    k = jax.random.normal(kk, (n_pages, page_size, K, hd), jnp.float32)
    v = jax.random.normal(kv, (n_pages, page_size, K, hd), jnp.float32)
    return k, v


def kernel_error(seed: int = 0) -> dict:
    """Max abs error of both kernels vs the dense float oracle."""
    H, K, hd, ps = 4, 2, 16, 8
    n_pages, B = 16, 3
    key = jax.random.key(seed)
    kq, kp, kb = jax.random.split(key, 3)
    k_pages, v_pages = _rand_pools(kp, n_pages, ps, K, hd)
    out = {}

    # --- decode: B requests, random lengths/tables --------------------------
    lengths = jnp.array([5, 17, 26], jnp.int32)
    rng = np.random.default_rng(seed)
    bt = np.zeros((B, n_pages), np.int32)
    used = rng.permutation(np.arange(1, n_pages))
    pos = 0
    for i in range(B):
        need = -(-int(lengths[i]) // ps)
        bt[i, :need] = used[pos:pos + need]
        pos += need
    bt = jnp.asarray(bt)
    q = jax.random.normal(kq, (B, H, hd), jnp.float32)

    def dense_decode(kp_, vp_):
        outs = []
        for i in range(B):
            n = -(-int(lengths[i]) // ps)
            kk = gather_pages(kp_, bt[i:i + 1, :n]).reshape(1, -1, K, hd)
            vv = gather_pages(vp_, bt[i:i + 1, :n]).reshape(1, -1, K, hd)
            outs.append(attention_ref(
                q[i:i + 1, None], kk, vv, causal=False,
                kv_len=lengths[i:i + 1],
            )[0, 0])
        return jnp.stack(outs)

    # the Pallas kernels themselves, in interpret mode on the CPU; the
    # ref path would be the oracle compared against itself
    got = paged_decode_attention(
        q, k_pages, v_pages, bt, lengths, interpret=True)
    out["decode_fp32"] = float(jnp.max(jnp.abs(got - dense_decode(
        k_pages, v_pages))))

    kq8, ks = quantize_kv_ref(k_pages)
    vq8, vs = quantize_kv_ref(v_pages)
    got8 = paged_decode_attention(
        q, kq8, vq8, bt, lengths, k_scales=ks, v_scales=vs, interpret=True)
    # oracle for int8 = dense attention over the *dequantized* pools
    out["decode_int8"] = float(jnp.max(jnp.abs(got8 - dense_decode(
        dequantize_pages_ref(kq8, ks), dequantize_pages_ref(vq8, vs)))))

    # --- fused chunked prefill: non-aligned past/chunk ----------------------
    past, C = 12, 7
    table = jnp.asarray(used[: -(-(past + C) // ps)].astype(np.int32))
    table = jnp.pad(table, (0, n_pages - table.shape[0]))
    qc = jax.random.normal(kb, (C, H, hd), jnp.float32)

    def dense_prefill(kp_, vp_):
        n = -(-(past + C) // ps)
        kk = gather_pages(kp_, table[None, :n]).reshape(1, -1, K, hd)
        vv = gather_pages(vp_, table[None, :n]).reshape(1, -1, K, hd)
        return attention_ref(
            qc[None], kk, vv, causal=True, q_offset=past,
            kv_len=jnp.array([past + C], jnp.int32),
        )[0]

    got = paged_prefill_attention(
        qc, k_pages, v_pages, table, past, interpret=True)
    out["prefill_fp32"] = float(jnp.max(jnp.abs(
        got - dense_prefill(k_pages, v_pages))))
    got8 = paged_prefill_attention(
        qc, kq8, vq8, table, past, k_scales=ks, v_scales=vs, interpret=True)
    out["prefill_int8"] = float(jnp.max(jnp.abs(got8 - dense_prefill(
        dequantize_pages_ref(kq8, ks), dequantize_pages_ref(vq8, vs)))))
    return out


# ---------------------------------------------------------------------------
# section 2: capacity + roofline at a byte budget
# ---------------------------------------------------------------------------
def capacity(cfg, page_size: int, budget_bytes: int) -> dict:
    """Pages/tokens per byte budget and the per-token read traffic."""
    out = {"budget_bytes": budget_bytes, "page_size": page_size}
    for dt in ("fp32", "int8"):
        pages = PagedLLMEngine.pages_for_byte_budget(
            cfg, page_size, budget_bytes, dt)
        out[dt] = {"pages": pages, "tokens": pages * page_size}
    out["capacity_ratio"] = round(
        out["int8"]["pages"] / max(out["fp32"]["pages"], 1), 3)
    # decode reads every resident KV byte once per token: traffic per
    # resident token is exactly the per-token storage footprint, so the
    # bandwidth ratio equals the inverse capacity ratio at fixed tokens
    K, hd = cfg.n_kv_heads, cfg.hd
    itemsize = jnp.zeros((), cfg.jdtype).dtype.itemsize
    fp32_tok = K * hd * itemsize * 2
    int8_tok = K * (hd * 1 + 4) * 2
    out["roofline"] = {
        "fp32_bytes_per_token": fp32_tok,
        "int8_bytes_per_token": int8_tok,
        "read_traffic_ratio": round(int8_tok / fp32_tok, 3),
    }
    return out


# ---------------------------------------------------------------------------
# section 3: serving at an equal byte budget
# ---------------------------------------------------------------------------
def serving(
    cfg,
    params,
    budget_bytes: int,
    n_requests: int = 16,
    prompt_len: int = 4,
    new_tokens: int = 20,
    page_size: int = 8,
    max_len: int = 96,
    seed: int = 3,
) -> dict:
    """fp32 vs int8 engines, same byte budget, same seeded burst.

    Admission is capacity-aware: a request enters only when the pool
    has un-reserved room for its *full* prompt+decode footprint, so
    ``max_concurrency`` measures how many requests the budget sustains
    side by side (not how many squeeze in before eviction churn).  Both
    engines run the identical policy; only the page count their byte
    budget buys differs.
    """
    out = {
        "budget_bytes": budget_bytes,
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "seed": seed,
    }
    # whole-lifetime footprint of one request, in pages
    need = -(-(prompt_len + new_tokens) // page_size)
    rows = []
    for dt in ("fp32", "int8"):
        pages = PagedLLMEngine.pages_for_byte_budget(
            cfg, page_size, budget_bytes, dt)
        eng = PagedLLMEngine(
            cfg, max_seqs=n_requests, max_len=max_len, page_size=page_size,
            num_pages=pages, params=params, kv_dtype=dt,
        )
        assert eng.page_bytes * pages <= budget_bytes
        cur_step = [0]
        finish_step = {}
        reserved = [0]

        def _done(req, _fs=finish_step, _cs=cur_step, _rv=reserved):
            _fs[req.rid] = _cs[0]
            _rv[0] -= need

        pending = deque(
            Request(rid=i, prompt=[1 + i % 7] * prompt_len,
                    max_new_tokens=new_tokens, on_finish=_done)
            for i in range(n_requests)
        )
        max_conc = 0
        t0 = time.perf_counter()
        while pending or eng.batch_size or eng.waiting:
            while (pending and reserved[0] + need < eng.num_pages
                   and eng.can_admit() and eng.admit(pending[0])):
                pending.popleft()
                reserved[0] += need
            max_conc = max(max_conc, eng.batch_size)
            if eng.batch_size or eng.waiting:
                eng.step()
            cur_step[0] += 1
        wall = time.perf_counter() - t0
        eng.allocator.check_no_leaks()
        jcts = [finish_step[i] for i in range(n_requests)]
        out[dt] = {
            "num_pages": pages,
            "pool_bytes": eng.page_bytes * pages,
            "max_concurrency": max_conc,
            "avg_jct_steps": round(float(np.mean(jcts)), 2),
            "p95_jct_steps": round(float(np.percentile(jcts, 95)), 2),
            "makespan_steps": cur_step[0],
            "preemptions": eng.preemptions,
            "wall_s": round(wall, 3),
        }
        rows.append([dt, pages, max_conc, out[dt]["avg_jct_steps"],
                     out[dt]["p95_jct_steps"], eng.preemptions])
    out["admission_gain"] = (
        out["int8"]["max_concurrency"] - out["fp32"]["max_concurrency"])
    out["jct_ratio"] = round(
        out["int8"]["avg_jct_steps"]
        / max(out["fp32"]["avg_jct_steps"], 1e-9), 3)
    # acceptance gates consumed by the nightly workflow
    out["pass_admission"] = out["admission_gain"] > 0
    out["pass_jct"] = out["jct_ratio"] <= 1.05
    emit_csv(
        f"fig11_serving (equal {budget_bytes}-byte KV budget, "
        f"{n_requests}-request burst; JCT in engine steps)",
        ["kv_dtype", "pages", "max_conc", "avg_jct_steps", "p95_jct_steps",
         "preemptions"],
        rows,
    )
    print(f"# int8 admission gain: +{out['admission_gain']} concurrent "
          f"(JCT ratio {out['jct_ratio']})\n")
    return out


def run(quick: bool = False, seed: int = 3, budget_bytes: int = 1 << 17) -> dict:
    """Run all three sections; write the fig11 artifact."""
    t0 = time.perf_counter()
    cfg = get_smoke_config("stablelm_1_6b")
    params = init_params(cfg, jax.random.key(0))[0]

    err = kernel_error()
    emit_csv(
        "fig11_kernel_error (max |kernel - oracle|)",
        ["case", "max_abs_err"],
        [[k, f"{v:.3e}"] for k, v in err.items()],
    )
    cap = capacity(cfg, page_size=8, budget_bytes=budget_bytes)
    emit_csv(
        f"fig11_capacity ({budget_bytes}-byte budget)",
        ["kv_dtype", "pages", "tokens", "bytes_per_token"],
        [
            ["fp32", cap["fp32"]["pages"], cap["fp32"]["tokens"],
             cap["roofline"]["fp32_bytes_per_token"]],
            ["int8", cap["int8"]["pages"], cap["int8"]["tokens"],
             cap["roofline"]["int8_bytes_per_token"]],
        ],
    )
    srv = serving(
        cfg, params, budget_bytes,
        n_requests=8 if quick else 16,
        seed=seed,
    )
    out = {
        "model": cfg.name,
        "kernel_error": err,
        "capacity": cap,
        "serving": srv,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    OUT_DIR.mkdir(exist_ok=True)
    with open(OUT_DIR / "fig11_kernels.json", "w") as f:
        json.dump(out, f, indent=2)
    print(f"# fig11 wall time: {out['wall_s']}s\n")
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--budget-bytes", type=int, default=1 << 17)
    args = ap.parse_args()
    run(quick=args.quick, seed=args.seed, budget_bytes=args.budget_bytes)
