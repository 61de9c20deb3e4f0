"""The one traffic generator: compound jobs and their arrivals.

A traffic file (``traffic/<name>.json``) gives the parameters:

- ``mix``: application name -> share.  Job structure (stages, output
  tokens, tool durations) comes from the program's application
  generators, which ``LLMSched``'s profiles are trained on;
- ``pool_seed``: the seed of the pool of jobs every run draws from;
- ``arrival``: ``{"kind": "poisson", "rate_jobs_per_s": r}`` for an
  open loop over the window, or ``{"kind": "batch", "jobs": n}`` for
  ``n`` jobs submitted at once;
- ``digest``: the sha256 of the first ``digest_jobs`` jobs of the pool
  and of the pool's arrival gaps.  A run whose pool hashes otherwise
  stops: a change to the generators cannot move the yardstick in
  silence.

Every seed serves the same jobs.  In a Poisson window the seed draws
the order of the jobs and of the same gaps between arrivals: the work
is fixed, the interleaving is not.  The window holds
``round(rate * seconds)`` jobs whose arrivals are spread over it as a
Poisson process conditioned on that count (normalised exponential
gaps).  A batch submits its jobs at once in the pool's own order, as an
offline sweep submits its dataset: which jobs a window of a backlog
reaches first is then the same for every seed, and so is the work.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, List, Tuple

import numpy as np


def _pool(traffic: Dict[str, Any], n: int):
    from repro.sim.workloads import get_generators

    gens = get_generators()
    names = list(traffic["mix"])
    p = np.array([traffic["mix"][k] for k in names], float)
    p /= p.sum()
    rng = np.random.default_rng(traffic["pool_seed"])
    jobs = []
    for _ in range(n):
        app = names[int(rng.choice(len(names), p=p))]
        jobs.append(gens[app].sample(rng, arrival_time=0.0))
    gaps = np.random.default_rng([traffic["pool_seed"], 1]).exponential(size=n + 1)
    return jobs, gaps


def _job_record(gj) -> Dict[str, Any]:
    job = gj.job
    stages = {}
    for name, st in sorted(job.stages.items()):
        stages[name] = {
            "execute": bool(st.will_execute),
            "tasks": [[t.is_llm, int(t.out_tokens), round(float(t.true_duration), 9)]
                      for t in st.tasks],
        }
    dyn = {k: [list(c), [list(e) for e in es]]
           for k, (c, es) in sorted(job.dynamic_realization.items())}
    inner = {k: {c: round(float(d), 9) for c, d in sorted(v.items())}
             for k, v in sorted(getattr(job, "_dyn_durs", {}).items())}
    return {"app": job.app.name, "stages": stages, "dynamic": dyn,
            "dynamic_durations": inner,
            "durations": {k: round(float(v), 9) for k, v in sorted(gj.durations.items())}}


def digest(traffic: Dict[str, Any]) -> str:
    """sha256 of the pool's first ``digest_jobs`` jobs and their gaps."""
    n = traffic["digest_jobs"]
    jobs, gaps = _pool(traffic, n)
    blob = json.dumps({"jobs": [_job_record(gj) for gj in jobs],
                       "gaps": [round(float(g), 12) for g in gaps]},
                      sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def n_jobs(traffic: Dict[str, Any], seconds: float) -> int:
    arr = traffic["arrival"]
    if arr["kind"] == "batch":
        return int(arr["jobs"])
    return max(1, int(round(arr["rate_jobs_per_s"] * seconds)))


def make_jobs(traffic: Dict[str, Any], seed: int, seconds: float,
              rate: float = None) -> Tuple[List[Any], int]:
    """The run's jobs, arrival times set, in the order ``seed`` draws
    (a batch: in the pool's order).

    Returns ``(jobs, n)``.  ``rate`` overrides the file's rate (the
    knee sweep).  Raises ``ValueError`` when the pool's digest differs
    from the file's.
    """
    got = digest(traffic)
    if got != traffic["digest"]:
        raise ValueError(f"traffic {traffic['name']}: pool digest {got} "
                         f"!= {traffic['digest']}: the job generators changed")
    arr = dict(traffic["arrival"])
    if rate is not None:
        arr = {"kind": "poisson", "rate_jobs_per_s": rate}
    n = n_jobs({"arrival": arr}, seconds)
    jobs, gaps = _pool(traffic, n)
    rng = np.random.default_rng(seed)
    if arr["kind"] == "batch":
        order, times = np.arange(n), np.zeros(n)
    else:
        order = rng.permutation(n)
        g = gaps[rng.permutation(n + 1)]
        times = np.cumsum(g)[:n] / g.sum() * seconds
    out = []
    for i, j in enumerate(order):
        gj = jobs[int(j)]
        gj.job.arrival_time = float(times[i])
        out.append(gj)
    return out, n


if __name__ == "__main__":
    import sys

    import harness

    for name in sys.argv[1:]:
        print(name, digest(harness.load_traffic(name)))
