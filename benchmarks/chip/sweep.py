#!/usr/bin/env python
"""Find a steady mix's knee: one window at each rate of a ladder.

    python benchmarks/chip/sweep.py --config <config> --traffic <mix> \
        --rates 0.5,1,2 --seconds 51

Builds the configuration's fleet once, then serves the traffic mix at
each rate in turn (open loop over ``--seconds``, then the drain), in one
process, and prints one JSON line per rate: jobs arrived and failed, the
drain after the window, and the metric readers' ``jct_mean_s``,
``jct_p90_s``, ``ttft_p95_ms``, ``decode_step_ms`` and ``sched_share``
(the same readers a cell's runs use).  The knee is the highest rate
whose backlog still drains in about one job's time and whose mean JCT
has not yet turned up steeply; a steady cell runs at 0.8 of it,
recorded in its traffic file.  Needs a TPU, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402
import run as RUN  # noqa: E402
import traffic as T  # noqa: E402
import window as W  # noqa: E402

READ = ({"name": "jct_mean_s", "unit": "s"}, {"name": "jct_p90_s", "unit": "s"},
        {"name": "ttft_p95_ms", "unit": "ms"}, {"name": "decode_step_ms", "unit": "ms"},
        {"name": "sched_share", "unit": "%"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    opts = ap.parse_args(argv)
    spec = H.load_config(opts.config)
    traffic = H.load_traffic(opts.traffic)
    import jax

    devices = RUN.check_devices(jax, int(spec["fleet"]["replicas"]))
    if devices is None:
        return RUN.EXIT_NO_CHIP
    RUN.enable_cache(jax)
    block = H.load_block(spec)
    serve, args, engines, _ = H.build_fleet(spec, traffic, opts.seed, devices, block)
    for rate in (float(r) for r in opts.rates.split(",")):
        jobs, n = T.make_jobs(traffic, opts.seed, opts.seconds, rate=rate)
        win = W.Window(seconds=opts.seconds, kind="steady")
        lat0 = [{b: len(v) for b, v in e._lat_samples.items()} for e in engines]
        skipped0 = sum(e.prefill_skipped_tokens for e in engines)
        restore = W.install(win, engines)
        try:
            serve.serve(args, engines=engines, workload=jobs)
        except W.WindowClosed:
            pass
        finally:
            restore()
        rec = RUN.measure(win, jobs, engines, lat0, skipped0)
        run = RUN.run_record(rec, "steady", opts.seconds, 0.0, None, [],
                             spec=spec, block=block)
        got = RUN.read_metrics(READ, run)
        print(json.dumps({
            "rate_jobs_per_s": rate, "jobs": n, "failed": rec.failed,
            "requests": len(win.requests), "drain_s": rec.t_close - opts.seconds,
            **{k: v["value"] for k, v in got.items()},
        }), flush=True)
        for eng in engines:          # leave no request behind for the next rate
            if eng.batch_size or eng.waiting:
                print(json.dumps({"note": "requests left in the engine; stopping"}))
                return 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
