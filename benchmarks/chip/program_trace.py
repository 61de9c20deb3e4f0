"""Reduce a profiler trace by the program's own ``repro:`` spans.

The program marks each phase of its served path with a ``repro:`` span
(``repro.tracing.SPANS``) on the profiler's clock.  Over the traced
window (the harness's ``bench:traced`` span, else the whole trace), this
returns:

- ``program_spans``: ``{name: [seconds, count]}`` of the spans that
  start inside the window, their seconds clipped to it;
- ``program_gaps``: device idle seconds by the innermost ``repro:`` span
  open at the middle of each gap (``none`` where no program span is
  open), cut with ``trace_reduce``'s own ``_segments``, so that the gaps
  add up to ``window_s - busy_s``;
- ``busy_s`` and ``window_s``, as ``trace_reduce.reduce_trace`` reads
  them.

A trace without program spans (a program that has none) gives empty
``program_spans`` and all idle time under ``none``.  Self-check on the
small recorded trace kept beside this file::

    python benchmarks/chip/program_trace.py --check
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import trace_reduce as TR  # noqa: E402

SAMPLE = HERE / "testdata" / "program_sample.xplane.pb"
SAMPLE_EXPECT = HERE / "testdata" / "program_sample.expect.json"
PROGRAM_PREFIX = "repro:"
NO_SPAN = "none"


def reduce_program(path: str) -> Dict:
    """Program spans and device idle by program span, over the window."""
    from jax.profiler import ProfileData

    devices: List[List[Tuple[int, int]]] = []
    window: List[Tuple[int, int]] = []     # the harness's bench:traced
    bench: List[Tuple[int, int]] = []      # every harness span
    prog: List[Tuple[int, int, str]] = []
    for plane in ProfileData.from_file(path).planes:
        if TR._is_device(plane.name):
            devices.append([(int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for ln in plane.lines if ln.name == "XLA Ops"
                            for e in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    s = int(ev.start_ns)
                    iv = (s, s + int(ev.duration_ns))
                    if ev.name.startswith(TR.SPAN_PREFIX):
                        bench.append(iv)
                        if ev.name == TR.SPAN_PREFIX + TR.WINDOW_SPAN:
                            window.append(iv)
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        prog.append((*iv, ev.name))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")

    gaps: Dict[str, float] = {}
    busy_s, win = [], []
    seg_t, seg_label = TR._segments(prog)
    for ivs in devices:
        if window:
            lo, hi = window[0]
        else:   # trace_reduce's fallback: the ops and the harness's spans
            lo = min(s for s, _ in ivs + bench)
            hi = max(e for _, e in ivs + bench)
        win.append((lo, hi))
        busy = TR._union([(max(s, lo), min(e, hi)) for s, e in ivs
                          if e > lo and s < hi])
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                k = bisect.bisect_right(seg_t, (prev + s) // 2) - 1
                label = seg_label[k] if k >= 0 else "loop"
                # _segments names the time under no span at all "loop"
                label = NO_SPAN if label == "loop" else label[len(PROGRAM_PREFIX):]
                gaps[label] = gaps.get(label, 0.0) + (s - prev) / 1e9 / len(devices)
            prev = max(prev, e)

    lo, hi = win[0]
    spans: Dict[str, List] = {}
    for s, e, name in prog:
        if lo <= s < hi:
            acc = spans.setdefault(name[len(PROGRAM_PREFIX):], [0.0, 0])
            acc[0] += (min(e, hi) - s) / 1e9
            acc[1] += 1
    return {
        "window_s": sum((b - a) / 1e9 for a, b in win) / len(win),
        "busy_s": sum(busy_s) / len(busy_s),
        "program_spans": dict(sorted(spans.items(), key=lambda kv: -kv[1][0])),
        "program_gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
    }


def _check() -> int:
    red = reduce_program(str(SAMPLE))
    ref = TR.reduce_trace(str(SAMPLE))
    want = json.loads(SAMPLE_EXPECT.read_text())
    bad = []
    for key in ("busy_s", "window_s"):
        for other, name in ((ref, "trace_reduce's"), (want, "expected")):
            if abs(red[key] - other[key]) > 1e-9 * max(1.0, abs(other[key])):
                bad.append(f"{key}: {red[key]} != {name} {other[key]}")
    idle = red["window_s"] - red["busy_s"]
    if abs(sum(red["program_gaps"].values()) - idle) > 1e-9:
        bad.append(f"program_gaps add up to {sum(red['program_gaps'].values())}, "
                   f"not the idle {idle}")
    for key in ("program_spans", "program_gaps"):
        if set(red[key]) != set(want[key]):
            bad.append(f"{key} names {sorted(red[key])} != {sorted(want[key])}")
    for k, (sec, n) in want["program_spans"].items():
        got = red["program_spans"].get(k, [0.0, 0])
        if got[1] != n or abs(got[0] - sec) > 1e-9:
            bad.append(f"program_spans[{k}]: {got} != {[sec, n]}")
    for k, v in want["program_gaps"].items():
        if abs(red["program_gaps"].get(k, 0.0) - v) > 1e-9:
            bad.append(f"program_gaps[{k}]: {red['program_gaps'].get(k)} != {v}")
    for line in bad:
        print("program_trace check FAILED:", line, file=sys.stderr)
    print(json.dumps({"ok": not bad, "busy_s": red["busy_s"],
                      "window_s": red["window_s"],
                      "program_gaps": red["program_gaps"]}))
    return 1 if bad else 0


def main(argv: Sequence[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?", help="a .xplane.pb or a directory holding one")
    ap.add_argument("--check", action="store_true",
                    help="reduce the recorded sample and compare with its expectation")
    ap.add_argument("--write-expect", action="store_true",
                    help="record the sample's reduction as its expectation")
    opts = ap.parse_args(argv)
    if opts.check:
        return _check()
    if opts.write_expect:
        red = reduce_program(str(SAMPLE))
        SAMPLE_EXPECT.write_text(json.dumps(
            {k: red[k] for k in ("busy_s", "window_s", "program_spans",
                                 "program_gaps")}, indent=1) + "\n")
        return 0
    if not opts.trace:
        ap.error("give a trace, --check or --write-expect")
    path = opts.trace if opts.trace.endswith(".pb") else TR.find_trace(opts.trace)
    print(json.dumps(reduce_program(path), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
