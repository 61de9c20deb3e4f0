#!/usr/bin/env python
"""Prove that the served path runs on a TPU, at the published width.

Drives ``repro.launch.serve``'s own path (``config_from_args`` ->
``build_engines`` -> ``build_scheduler`` -> ``ServingCluster.run``)
with stablelm-1.6b at its published width and random weights from
``--seed``.  Everything runs in this one process: a chip belongs to one
process at a time, so no child is ever started.

One chip (no arguments):

1. device check: the first JAX device must be a TPU; there is no CPU
   fallback.
2. kernels: the paged decode and paged prefill Pallas kernels at
   stablelm-1.6b widths (32 heads of 64, 16-token pages) and at
   internlm2-20b's (48 heads over 8 of 128), bf16 and int8 pools stored
   as the model step stores them (stacked over layers, read at layer
   1), and the in-place scale writer, against the ``kernels/ref.py``
   oracles in float32.
3. served run: one paged replica (4 sequences of up to 512 tokens),
   LLMSched over the planning mix, a 160-token shared system prompt and
   the prefix cache.  Every step program is compiled first and timed
   apart as set-up; the run has the KV sanitizer on, so a non-finite
   logit or a page-ownership fault fails it.  Checks that every job
   finishes, that the tokens decoded match the budgets, that the
   lowered decode and prefill steps hold Pallas kernels
   (``tpu_custom_call``), and that a real copy-on-write prefix hit
   decodes its full budget.

``--chips 4`` runs only the multi-replica phase: four replicas, one per
chip, behind LLMSched with live migration.  Checks that each replica's
weights and pools sit on its own chip, that every job finishes, that
every chip decoded tokens, and that a request migrated from chip 0 to
chip 1 mid-decode continues token for token as the same request decoded
on chip 0 alone.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check exits non-zero before it is printed.

The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when
that is set, else ``<repo>/.jax_cache``; a second run reuses it.

Usage::

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips of one host
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Largest |kernel - oracle| admitted for bf16 inputs.  The oracle runs in
# float32 at full matmul precision; the kernels round their output to
# bf16 (relative 2^-9) and may feed bf16 softmax weights to the MXU
# (relative 2^-8), on outputs of magnitude below 1.
KERNEL_TOL = 2e-2

# page geometry and widths of stablelm-1.6b (H = K = 32, hd = 64)
N_HEADS, HEAD_DIM, PAGE = 32, 64, 16

SERVE_ARGV = [
    "--arch", "stablelm-1.6b", "--engine", "paged", "--mix", "planning",
    "--jobs", "6", "--max-batch", "4", "--max-len", "512",
    "--prefix-cache", "--shared-prompt", "160",
]
MULTI_ARGV = [
    "--arch", "stablelm-1.6b", "--engine", "paged", "--mix", "planning",
    "--jobs", "8", "--replicas", "4", "--max-batch", "2", "--max-len", "512",
    "--migrate",
]
# the cluster's prompt is the shared system prompt plus a 2-token suffix
SUFFIX = 2


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, msg: str) -> None:
    """Raise :class:`SmokeFailure` with ``msg`` unless ``ok``."""
    if not ok:
        raise SmokeFailure(msg)


def say(**kv) -> None:
    """Print one ``[chip_smoke] key=value ...`` line."""
    print("[chip_smoke] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileLog:
    """Counts backend compiles and persistent-cache hits (thread-safe)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self.compiles += 1
                self.seconds += duration

    def on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.seconds, self.cache_hits


# ---------------------------------------------------------------------------
# phase 2: kernels against their oracles
# ---------------------------------------------------------------------------
def kernel_phase(seed: int) -> None:
    """Paged decode/prefill kernels, bf16 and int8, against the oracles."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.paged_attention import (
        paged_decode_attention,
        paged_prefill_attention,
        write_scales,
    )

    B, npp, past, C = 4, 32, 64, 64
    P = 1 + B * npp
    rng = np.random.default_rng(seed)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    cases = {}
    # stablelm-1.6b's MHA, and internlm2-20b's GQA
    for H, K, hd in ((N_HEADS, N_HEADS, HEAD_DIM), (48, 8, 128)):
        keys = jax.random.split(jax.random.fold_in(jax.random.key(seed), K), 4)
        # two layers: the kernels read layer 1, layer 0 holds other pages
        shape = (2, P, PAGE, K, hd)
        k_pages = jax.random.normal(keys[0], shape, jnp.bfloat16)
        v_pages = jax.random.normal(keys[1], shape, jnp.bfloat16)
        q_dec = jax.random.normal(keys[2], (B, H, hd), jnp.bfloat16)
        q_pre = jax.random.normal(keys[3], (C, H, hd), jnp.bfloat16)
        tables = jnp.asarray(
            1 + rng.permutation(P - 1)[: B * npp].reshape(B, npp), jnp.int32
        )
        lengths = jnp.asarray(rng.integers(1, npp * PAGE + 1, B), jnp.int32)
        kq, ks = ref.quantize_kv_ref(k_pages)
        vq, vs = ref.quantize_kv_ref(v_pages)
        kp, vp = ref.stored_pages(k_pages), ref.stored_pages(v_pages)
        kq, vq = ref.stored_pages(kq), ref.stored_pages(vq)
        sc = dict(k_scales=ref.stored_scales(ks), v_scales=ref.stored_scales(vs))
        w = f"{H}x{hd}"
        cases.update({
            f"decode_bf16_{w}": (
                lambda q=q_dec, kp=kp, vp=vp, t=tables, n=lengths:
                    paged_decode_attention(q, kp, vp, t, n, 1),
                lambda q=q_dec, kp=kp, vp=vp, t=tables, n=lengths:
                    ref.paged_decode_attention_ref(
                        f32(q), f32(kp), f32(vp), t, n, 1),
            ),
            f"decode_int8_{w}": (
                lambda q=q_dec, kq=kq, vq=vq, t=tables, n=lengths, sc=sc:
                    paged_decode_attention(q, kq, vq, t, n, 1, **sc),
                lambda q=q_dec, kq=kq, vq=vq, t=tables, n=lengths, sc=sc:
                    ref.paged_decode_attention_ref(f32(q), kq, vq, t, n, 1, **sc),
            ),
            f"prefill_bf16_{w}": (
                lambda q=q_pre, kp=kp, vp=vp, t=tables:
                    paged_prefill_attention(q, kp, vp, t[0], past, 1),
                lambda q=q_pre, kp=kp, vp=vp, t=tables:
                    ref.paged_prefill_attention_ref(
                        f32(q), f32(kp), f32(vp), t[0], past, 1),
            ),
            f"prefill_int8_{w}": (
                lambda q=q_pre, kq=kq, vq=vq, t=tables, sc=sc:
                    paged_prefill_attention(q, kq, vq, t[0], past, 1, **sc),
                lambda q=q_pre, kq=kq, vq=vq, t=tables, sc=sc:
                    ref.paged_prefill_attention_ref(
                        f32(q), kq, vq, t[0], past, 1, **sc),
            ),
        })
        # a prefill chunk's scales over consecutive pages, written in place
        pages = jnp.repeat(tables[0, :4], PAGE // 2)
        slots = jnp.tile(jnp.arange(PAGE // 2, dtype=jnp.int32) * 2, 4)
        vals = jax.random.uniform(keys[0], (pages.shape[0], K), jnp.float32)
        cases[f"write_scales_{w}"] = (
            lambda s=sc["k_scales"], p=pages, sl=slots, v=vals:
                write_scales(s, 1, p, sl, v),
            lambda s=sc["k_scales"], p=pages, sl=slots, v=vals:
                ref.write_scales_ref(s, 1, p, sl, v),
        )
    for name, (kernel, oracle) in cases.items():
        got = f32(kernel())
        with jax.default_matmul_precision("highest"):
            want = f32(oracle())
        err = float(jnp.max(jnp.abs(got - want)))
        say(phase="kernels", case=name, max_abs_err=err, tol=KERNEL_TOL)
        check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
        check(err <= KERNEL_TOL, f"{name}: max abs error {err} > {KERNEL_TOL}")


# ---------------------------------------------------------------------------
# shared pieces of the served phases
# ---------------------------------------------------------------------------
def build_fleet(serve, argv, seed, prompt_lens, cached_tokens):
    """Parse ``argv``, build its engines and compile every step program.

    Returns ``(args, engines, setup_seconds)``; compiles run one thread
    per replica so that replicas on separate chips compile side by side.
    """
    from repro.serving import build_engines

    args = serve.build_parser().parse_args(argv + ["--seed", str(seed)])
    t0 = time.perf_counter()
    engines = build_engines(serve.model_config(args), serve.config_from_args(args))
    with ThreadPoolExecutor(len(engines)) as pool:
        for fut in [pool.submit(e.warm_up, prompt_lens, cached_tokens)
                    for e in engines]:
            fut.result()
    return args, engines, time.perf_counter() - t0


def served_run(serve, args, engines, log: CompileLog):
    """Serve ``args``' workload on the warmed ``engines``; check the result."""
    from repro.core.dag import TaskState

    workload = serve.workload_from_args(args)
    serve_cfg = serve.config_from_args(args)
    n0, s0, _ = log.snapshot()
    t0 = time.perf_counter()
    res = serve.serve(args, engines=engines, workload=workload)
    wall = time.perf_counter() - t0
    n1, s1, _ = log.snapshot()
    budget = sum(
        max(serve_cfg.min_tokens, int(t.out_tokens / serve_cfg.token_scale))
        for gj in workload
        for stage in gj.job.stages.values()
        for t in stage.tasks
        if t.is_llm and t.state is TaskState.DONE
    )
    say(phase="serve", jobs=len(workload), finished=len(res.jcts),
        avg_jct_s=res.avg_jct, makespan_s=res.makespan, run_wall_s=wall,
        tokens=res.tokens_generated, token_budget=budget,
        prefill_tokens=res.prefill_tokens,
        prefill_saved_tokens=res.prefill_saved_tokens,
        migrations=res.migrations, preemptions=res.preemptions,
        compiles_in_run=n1 - n0, compile_s_in_run=s1 - s0)
    check(len(res.jcts) == len(workload),
          f"{len(res.jcts)} of {len(workload)} jobs finished")
    check(all(gj.job.done() for gj in workload), "a job is not done")
    check(res.tokens_generated == budget,
          f"decoded {res.tokens_generated} tokens, budgets sum to {budget}")
    return res


def drive(eng, req, until=None, limit: int = 4096) -> None:
    """Step ``eng`` until ``req`` is done, or until ``until(req)`` holds."""
    stop = until or (lambda r: r.done())
    for _ in range(limit):
        if stop(req):
            return
        eng.step()
    raise SmokeFailure(f"request {req.rid} did not finish in {limit} steps")


def lowered_has_kernels(eng) -> None:
    """Check that the replica's decode and prefill programs call Pallas."""
    import jax.numpy as jnp

    bt = jnp.zeros((eng.pages_per_seq,), jnp.int32)
    dec = eng._decode.lower(
        eng.params, eng.pools, jnp.zeros((1,), jnp.int32), bt[None],
        jnp.zeros((1,), jnp.int32),
    ).as_text()
    pre = eng._prefill_fn(0).lower(
        eng.params, eng.pools, jnp.zeros((1, eng.prefill_chunk), jnp.int32), bt,
    ).as_text()
    found = {"decode": dec.count("tpu_custom_call"),
             "prefill": pre.count("tpu_custom_call")}
    say(phase="lowering", **{f"{k}_tpu_custom_calls": v for k, v in found.items()})
    for step, n in found.items():
        check(n > 0, f"the lowered {step} step calls no Pallas kernel")


# ---------------------------------------------------------------------------
# phase 3: one chip
# ---------------------------------------------------------------------------
def one_chip_phase(serve, seed: int, log: CompileLog) -> None:
    """Full-width served run on one replica, plus a copy-on-write probe."""
    from repro.serving import Request

    argv = SERVE_ARGV
    shared = int(argv[argv.index("--shared-prompt") + 1])
    # the probe's page-aligned prompt is a full prefix-cache hit, whose
    # last prompt token re-runs inside the shared page: copy-on-write
    args, (eng,), setup = build_fleet(
        serve, argv, seed, [shared + SUFFIX, shared], (0, shared)
    )
    n, secs, hits = log.snapshot()
    say(phase="setup", build_and_compile_s=setup, compiles=n, compile_s=secs,
        persistent_cache_hits=hits)
    served_run(serve, args, [eng], log)
    lowered_has_kernels(eng)

    rng = np.random.default_rng(seed)
    prompt = [int(t) for t in rng.integers(1, eng.cfg.vocab, shared)]
    first = Request(rid=10_001, prompt=prompt, max_new_tokens=8)
    again = Request(rid=10_002, prompt=list(prompt), max_new_tokens=8)
    cow0 = eng.cow_copies
    for req in (first, again):
        check(eng.admit(req), f"probe request {req.rid} refused")
        drive(eng, req)
    say(phase="cow_probe", cow_copies=eng.cow_copies - cow0,
        tokens=len(again.out_tokens), same_as_uncached=again.out_tokens == first.out_tokens)
    check(eng.cow_copies > cow0, "the prefix hit made no copy-on-write")
    check(len(again.out_tokens) == again.max_new_tokens,
          "the copy-on-write request stopped short of its budget")
    eng.allocator.check_no_leaks(allow_indexed=True)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def multi_chip_phase(serve, seed: int, log: CompileLog) -> None:
    """Four replicas, one per chip, with live migration across chips."""
    import jax

    from repro.serving import Request

    args, engines, setup = build_fleet(serve, MULTI_ARGV, seed, [SUFFIX], (0,))
    n, secs, hits = log.snapshot()
    say(phase="setup", build_and_compile_s=setup, compiles=n, compile_s=secs,
        persistent_cache_hits=hits)
    homes = []
    for i, eng in enumerate(engines):
        held = {d for leaf in jax.tree.leaves((eng.params, eng.pools))
                for d in leaf.devices()}
        say(phase="placement", replica=i, device=eng.device.id,
            array_devices=sorted(d.id for d in held))
        check(held == {eng.device}, f"replica {i} holds arrays on {held}")
        homes.append(eng.device)
    check(len(set(homes)) == len(engines), f"replicas share devices: {homes}")

    served_run(serve, args, engines, log)
    decoded = [e.decoded_tokens for e in engines]
    say(phase="per_chip", decoded_tokens=decoded)
    check(all(d > 0 for d in decoded), f"a chip decoded nothing: {decoded}")
    lowered_has_kernels(engines[0])

    # one request decoded alone on chip 0, and the same request moved to
    # chip 1 after a few tokens: both decode at bucket 1, same program
    src, dst = engines[0], engines[1]
    prompt = [3, 5]
    alone = Request(rid=20_001, prompt=prompt, max_new_tokens=24)
    moved = Request(rid=20_002, prompt=list(prompt), max_new_tokens=24)
    check(src.admit(alone), "reference request refused")
    drive(src, alone)
    check(src.admit(moved), "migrating request refused")
    drive(src, moved, until=lambda r: len(r.out_tokens) >= 8)
    ticket = src.export_request(src.youngest_active_row())
    check(dst.import_request(ticket), "chip 1 refused the migration ticket")
    drive(dst, moved)
    same = moved.out_tokens == alone.out_tokens
    say(phase="migration", src=src.device.id, dst=dst.device.id,
        migrated_after=8, tokens=len(moved.out_tokens), equal=same)
    check(same, f"migrated {moved.out_tokens} != unmigrated {alone.out_tokens}")


def main(argv=None) -> int:
    """Run the phases; print the device line last.  Returns the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica phase")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devices[0].platform} ({devices[0].device_kind})",
              file=sys.stderr)
        return 1
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    try:
        from repro.launch import serve
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from {ROOT}/src: "
              f"{e}", file=sys.stderr)
        return 1

    say(device_kind=devices[0].device_kind, devices=len(devices),
        compile_cache=serve.enable_compile_cache())
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)
    os.environ["REPRO_SANITIZE"] = "1"   # engines built from here on check
    t0 = time.perf_counter()
    try:
        if opts.chips == 4:
            multi_chip_phase(serve, opts.seed, log)
        else:
            kernel_phase(opts.seed)
            one_chip_phase(serve, opts.seed, log)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    n, secs, hits = log.snapshot()
    say(total_s=time.perf_counter() - t0, compiles=n, compile_s=secs,
        persistent_cache_hits=hits)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
