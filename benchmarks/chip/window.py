"""The measured window: the harness's own wrappers around each layer.

The program is not edited.  For one served run the harness wraps, on
the instances the run uses:

- ``ServingCluster.run``: marks the window's start (the clock origin of
  every time below, within microseconds of the cluster's own) and its
  end;
- ``scheduler.schedule``: host seconds per call (the same span the
  cluster's ``sched_overhead_s`` times), and, between loop iterations,
  the start and stop of the profiler and the close of the window;
- each engine's ``admit`` and ``step``: the requests admitted, and the
  moment each one's first output token exists on the host;
- with tracing on, each engine's decode step and prefill chunk
  programs: the rows' context lengths and the chunks, for the FLOP and
  byte counts, and ``bench:*`` spans in the profiler's trace.

A window closes by raising :class:`WindowClosed` from the scheduler
wrapper: in a batch window when ``seconds`` have passed, in a steady
window at the drain bound if jobs are still open.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

# One drain bound for every steady cell: after the last arrival, the run
# waits at most this long for the open jobs; a job still open then has
# failed.
DRAIN_S = 150.0
# The traced part of a --trace 1 run: TRACE_S seconds from the first
# loop iteration at or after TRACE_AT of the window in which a replica
# holds requests (so that the trace has steps to read).
TRACE_AT = 0.5
TRACE_S = 4.0


class WindowClosed(Exception):
    """Raised inside the cluster's loop to end the measured window."""


@dataclass
class Window:
    """What one served run measured, on the harness's clock."""

    seconds: float
    kind: str                          # "steady" | "batch"
    trace_dir: Optional[str] = None
    close_at: float = 0.0
    t_run: float = 0.0                 # perf_counter at the window's start
    t_close: float = 0.0               # seconds from t_run to the close
    closed_early: bool = False
    sched_s: float = 0.0
    sched_calls: int = 0
    requests: List[Any] = field(default_factory=list)
    first_token: Dict[int, float] = field(default_factory=dict)
    pending: List[Any] = field(default_factory=list)
    steps: List[tuple] = field(default_factory=list)
    tracing: bool = False
    traced: Optional[tuple] = None     # (start, stop) seconds
    cluster: Any = None
    busy: Any = None                   # () -> bool: a replica holds requests
    _span: Any = None

    def __post_init__(self):
        self.close_at = self.seconds + (DRAIN_S if self.kind == "steady" else 0.0)

    def now(self) -> float:
        return time.perf_counter() - self.t_run

    def ann(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench:{name}")

    # -- profiler --------------------------------------------------------
    def _start_trace(self) -> None:
        import jax
        jax.profiler.start_trace(self.trace_dir)
        self._span = jax.profiler.TraceAnnotation("bench:traced")
        self._span.__enter__()
        self.tracing = True
        self.traced = (self.now(), None)

    def _stop_trace(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        self.traced = (self.traced[0], self.now())
        self.tracing = False
        jax.profiler.stop_trace()

    def tick(self) -> None:
        """Between loop iterations: drive the profiler, close the window."""
        t = self.now()
        if self.trace_dir is not None:
            start = TRACE_AT * self.seconds
            if self.traced is None and t >= start and self.busy():
                self._start_trace()
            elif self.tracing and t >= start + TRACE_S:
                self._stop_trace()
        if t >= self.close_at:
            if self.tracing:
                self._stop_trace()
            self.closed_early = True
            raise WindowClosed()


def install(win: Window, engines) -> callable:
    """Wrap the cluster class and the engines; return the undo function."""
    from repro.serving import cluster as cluster_mod

    cls = cluster_mod.ServingCluster
    orig_run = cls.run

    def run(self, workload):
        win.cluster = self
        sched = self.scheduler
        orig_schedule = sched.schedule

        def schedule(active, view):
            win.tick()
            with win.ann("schedule"):
                t0 = time.perf_counter()
                dec = orig_schedule(active, view)
                win.sched_s += time.perf_counter() - t0
            win.sched_calls += 1
            return dec

        sched.schedule = schedule
        win.t_run = time.perf_counter()
        try:
            return orig_run(self, workload)
        finally:
            win.t_close = win.now()
            if win.tracing:
                win._stop_trace()

    cls.run = run
    win.busy = lambda: any(e.batch_size or e.waiting for e in engines)
    undo = [lambda: setattr(cls, "run", orig_run)]
    for eng in engines:
        undo.append(_wrap_engine(win, eng))

    def restore():
        for u in undo:
            u()
    return restore


def _wrap_engine(win: Window, eng):
    orig_admit, orig_step = eng.admit, eng.step
    orig_decode, orig_prefill_fn = eng._decode, eng._prefill_fn

    def admit(req):
        with win.ann("admit"):
            ok = orig_admit(req)
        if ok:
            win.requests.append(req)
            if req.out_tokens:
                win.first_token[id(req)] = win.now()
            else:
                win.pending.append(req)
        return ok

    def step():
        with win.ann("engine.step"):
            out = orig_step()
        if win.pending:
            t = win.now()
            still = []
            for req in win.pending:
                if req.out_tokens:
                    win.first_token[id(req)] = t
                else:
                    still.append(req)
            win.pending = still
        return out

    def decode(params, pools, toks, bt, lens):
        if win.tracing:
            lv = np.asarray(lens)
            win.steps.append(("decode", [int(x) + 1 for x in lv if x > 0]))
        with win.ann("decode_call"):
            return orig_decode(params, pools, toks, bt, lens)

    def prefill_fn(past):
        fn = orig_prefill_fn(past)

        def call(params, pools, toks, bt):
            if win.tracing:
                win.steps.append(("prefill", int(past), int(toks.shape[1])))
            with win.ann("prefill_call"):
                return fn(params, pools, toks, bt)
        return call

    eng.admit, eng.step = admit, step
    eng._decode, eng._prefill_fn = decode, prefill_fn

    def undo():
        for name in ("admit", "step", "_decode", "_prefill_fn"):
            eng.__dict__.pop(name, None)
        eng._decode = orig_decode
    return undo
