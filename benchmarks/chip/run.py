#!/usr/bin/env python
"""Run one cell of the chip benchmark; print its result as the last line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix, found by name under ``benchmarks/chip``.  The run serves the
mix through the program's own path, ``repro.launch.serve.serve()`` ->
``ServingCluster.run`` -> ``LLMSched`` -> ``PagedLLMEngine`` -> the
Pallas paged kernels, on random weights made from ``--seed``:

1. set-up: weights, replicas, every step program compiled (or loaded
   from the persistent cache in ``<checkout>/.jax_cache``) and run once,
   the scheduler's profiles fitted;
2. the window: ``--seconds`` of arrivals (a steady mix then drains, up
   to one drain bound; a batch mix stops at the window's end);
3. the check: a sample of finished requests, drawn from the seed with
   the longest among them, re-run through a float32 reference; the
   widest gap by which a served token's logit lies below the
   reference's best is held to the cell's limit, and no stage may have
   started before its parents finished.

``--trace 1`` profiles a part of the window and reports the per-layer
metrics instead of the end-to-end ones.  ``--control`` puts the fp8
control in the program's place in step 3; such a run must come out not
correct.  The run needs a TPU: with no TPU, or fewer chips than the
cell asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402
import reference as R  # noqa: E402
import traffic as T  # noqa: E402
import window as W  # noqa: E402

SAMPLE_REQUESTS = 8
EXIT_NO_CHIP = 3
EXIT_ERROR = 2


class Compiles:
    """Counts backend compiles (to show none happen inside the window)."""

    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.n += 1


def enable_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(H.ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def check_devices(jax, chips: int):
    """The devices the cell runs on; ``None`` (and a message) if unfit."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"run.py: JAX found no device: {e}", file=sys.stderr)
        return None
    if devs[0].platform != "tpu":
        print(f"run.py: no TPU: JAX's first device is {devs[0].platform} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return None
    return devs[:chips]


def stage_ready(job, stage_name: str) -> float:
    """When a stage could first run: its job's arrival for a root stage,
    else the latest finish among its parents' tasks (cluster clock)."""
    ready = job.arrival_time
    for p in job.parents_of(stage_name):
        ps = job.stages.get(p)
        if ps is not None:
            for t in ps.tasks:
                if t.finish_time >= 0:
                    ready = max(ready, t.finish_time)
    return ready


def pair_requests(win, jobs):
    """Pair each admitted request with its LLM task, in admission order.

    With one loop thread, successful admissions and the tasks' start
    stamps come in the same order; the pairing is checked against each
    request's budget.
    """
    tasks = sorted(
        ((t.start_time, t, gj.job) for gj in jobs
         for st in gj.job.stages.values() for t in st.tasks
         if t.is_llm and t.start_time >= 0),
        key=lambda x: x[0])
    if len(tasks) != len(win.requests):
        raise RuntimeError(f"{len(win.requests)} requests admitted for "
                           f"{len(tasks)} started LLM tasks")
    pairs = []
    for req, (_, task, job) in zip(win.requests, tasks):
        if req.max_new_tokens != max(2, int(task.out_tokens)):
            raise RuntimeError("request/task pairing lost its order")
        pairs.append((req, task, job))
    return pairs


def measure(win, jobs, engines, lat0, skipped0):
    """The run's host-clock record, for the metric readers.

    ``out_tokens`` counts the output tokens the window's requests hold
    at its close, finished or in flight: tokens a preempted request
    threw away (the engine restarts it from its prompt) are not served
    output and do not count.
    """
    t_close = win.t_close
    jcts = []
    failed = 0
    for gj in jobs:
        job = gj.job
        if job.finish_time >= 0:
            jcts.append(job.finish_time - job.arrival_time)
        elif win.kind == "steady":
            failed += 1
            jcts.append(t_close - job.arrival_time)
    ttft = []
    prefill_run = 0
    for req, task, job in pair_requests(win, jobs):
        prefill_run += req.prefill_tokens
        first = win.first_token.get(id(req))
        ready = stage_ready(job, task.stage_name)
        ttft.append((first if first is not None else t_close) - ready)
    lat, rows = _new_samples(engines, lat0)
    return SimpleNamespace(
        jcts=jcts, failed=failed, ttft_s=ttft, lat_samples=lat,
        decode_rows=rows, prefill_run=prefill_run,
        prefill_skipped=sum(e.prefill_skipped_tokens for e in engines) - skipped0,
        out_tokens=sum(len(r.out_tokens) for r in win.requests),
        sched_s=win.sched_s, t_close=t_close,
    )


def _new_samples(engines, lat0):
    """The window's decode-step samples: seconds, and rows of each step."""
    secs, rows = [], []
    for eng, before in zip(engines, lat0):
        for b, vs in eng._lat_samples.items():
            new = vs[before.get(b, 0):]
            secs.extend(new)
            rows.extend([b] * len(new))
    return secs, rows


def run_record(rec, kind: str, seconds: float, setup_s: float, peaks, steps,
               spec=None, block=None) -> SimpleNamespace:
    """What every metric reader reads: the host record and the traced
    steps; ``trace`` is set once a trace has been reduced.  ``block``
    (the configuration's block module) counts the steps' work from
    ``spec`` (its configuration file)."""
    return SimpleNamespace(kind=kind, seconds=seconds, setup_s=setup_s, peaks=peaks,
                           steps=steps, trace=None, spec=spec, block=block,
                           **vars(rec))


def read_metrics(entries, run) -> dict:
    """``{name: {value, unit}}`` for each metric entry whose reader finds
    something to read in ``run``."""
    out = {}
    for m in entries:
        val = H.load_reader(m["name"])(run)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def sample_requests(win, seed: int):
    """The longest finished request and others drawn from the seed."""
    done = [r for r in win.requests if r.done() and r.out_tokens]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i].out_tokens))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng(H.derive_seed(seed, 3))
    pick = rng.choice(len(rest), size=min(len(rest), SAMPLE_REQUESTS - 1),
                      replace=False) if rest else []
    chosen = [done[longest]] + [done[rest[int(i)]] for i in pick]
    return [(list(r.prompt), list(r.out_tokens)) for r in chosen]


def run_cell(opts, spec, block, traffic, limits, devices, compiles=None,
             on_engines=None):
    """Serve one window and check it.  Returns the result dictionary.

    ``block`` is the configuration's block module (``H.load_block``)."""
    import jax

    kind = traffic["window"]
    jobs, n = T.make_jobs(traffic, opts.seed, opts.seconds)
    serve, args, engines, weights = H.build_fleet(spec, traffic, opts.seed, devices,
                                                  block)
    if on_engines is not None:
        on_engines(engines)
    trace_dir = None
    if opts.trace:
        trace_dir = str(H.ROOT / ".bench_out" / "trace" / opts.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = W.Window(seconds=float(opts.seconds), kind=kind, trace_dir=trace_dir)
    lat0 = [{b: len(v) for b, v in e._lat_samples.items()} for e in engines]
    skipped0 = sum(e.prefill_skipped_tokens for e in engines)
    restore = W.install(win, engines)
    c0 = compiles.n if compiles else 0
    try:
        # serve() fits the scheduler's profiles, then enters the window
        serve.serve(args, engines=engines, workload=jobs)
    except W.WindowClosed:
        pass
    finally:
        restore()
    setup_s = win.t_run - T_PROC
    compiles_in_window = (compiles.n - c0) if compiles else 0
    rec = measure(win, jobs, engines, lat0, skipped0)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    run = run_record(rec, kind, float(opts.seconds), setup_s, opts.peaks, win.steps,
                     spec=spec, block=block)
    red = None
    if trace_dir is not None:
        import trace_reduce
        red = trace_reduce.reduce_trace(trace_reduce.find_trace(trace_dir))
        run.trace = red
        if not opts.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # free the program's state before the reference runs
    order_bad = R.order_violations(jobs)
    seqs = sample_requests(win, opts.seed)
    for eng in engines:
        eng.pools = None
        eng.params = None
    win.cluster = None
    del engines
    jax.clear_caches()
    t_ref = time.perf_counter()
    control = bool(getattr(opts, "control", False))
    gaps = R.logit_gaps(weights, spec, block, seqs, spec["fleet"]["max_len"],
                        control=control)
    t_ref = time.perf_counter() - t_ref

    # with --control the fp8 control stands in the program's place: its
    # gap goes through the same check, and the run must come out false
    gap = gaps["control_max_gap"] if control else gaps["max_gap"]
    lim_gap = limits["max_logit_gap"]["limit"]
    checks = {
        "max_logit_gap": {"value": gap, "limit": lim_gap, "rule": "<="},
        "order_violations": {"value": order_bad, "limit": 0, "rule": "<="},
        "sampled_tokens": {"value": gaps["tokens"], "limit": 1, "rule": ">="},
    }
    correct = gap <= lim_gap and order_bad == 0 and gaps["tokens"] >= 1
    metrics = read_metrics(
        H.cell_metrics(opts.bench, opts.workload, bool(opts.trace)), run)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "device_kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(mem)}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    out = {"correct": bool(correct), "attempted": n, "failed": rec.failed,
           "metrics": metrics, "device": device}
    if red is not None:
        import trace_reduce
        out["breakdown"] = trace_reduce.breakdown(red)
    out["info"] = {"compiles_in_window": compiles_in_window,
                   "window_closed_at_s": rec.t_close,
                   "closed_by_bound": win.closed_early,
                   "jobs_finished": sum(1 for gj in jobs if gj.job.finish_time >= 0),
                   "requests": len(win.requests), "out_tokens": rec.out_tokens,
                   "sched_calls": win.sched_calls, "reference_s": t_ref}
    if control:
        out["info"]["control"] = "fp8 reference in the program's place"
        out["info"]["program_max_gap"] = gaps["max_gap"]
    if red is not None:
        out["info"]["program_s"] = red["program_s"]
        out["info"]["kernel_s"] = red["kernel_s"]
        out["info"]["longest_gaps"] = red["longest_gaps"]
        out["info"]["traced_steps"] = len(win.steps)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="judge the fp8 control in the program's place: the "
                         "check reads the token the control puts first at "
                         "each served position, and must come out false "
                         "(not part of the benchmark's runs)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler trace under .bench_out/trace")
    opts = ap.parse_args(argv)
    try:
        opts.bench = H.load_benchmark()
        cell = H.find_cell(opts.bench, opts.workload)
        spec = H.load_config(cell["config"])
        traffic = H.load_traffic(cell["traffic"])
        limits = H.load_limits(opts.workload)
        block = H.load_block(spec)
    except (H.BenchError, json.JSONDecodeError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return EXIT_ERROR
    if os.environ.get("REPRO_SANITIZE"):
        print("run.py: REPRO_SANITIZE is set; the benchmark serves with the "
              "KV sanitizer off", file=sys.stderr)
        return EXIT_ERROR
    try:
        import jax
    except ImportError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return EXIT_ERROR
    devices = check_devices(jax, int(cell["chips"]))
    if devices is None:
        return EXIT_NO_CHIP
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run.py: cannot import the program from {H.SRC}: {e}", file=sys.stderr)
        return EXIT_ERROR
    from peaks import peaks_for
    opts.peaks = peaks_for(devices[0].device_kind)
    enable_cache(jax)
    compiles = Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        out = run_cell(opts, spec, block, traffic, limits, devices, compiles)
    except H.BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return EXIT_ERROR
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
