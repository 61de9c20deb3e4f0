"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData`` alone and returns:

- ``busy_s`` / ``window_s``: the union of the intervals in which an
  operation ran on each device, averaged over the devices, and the
  length of the traced window (the harness's ``bench:traced`` span);
- ``ops``: device seconds by kind of operation (``copy``, ``fusion``,
  ``paged_decode_attention``, ...), control-flow containers left out;
- ``kernel_s``: device seconds of each kernel in ``KERNELS``, found by
  a substring of the op's name or of its string stats;
- ``program_s``: device seconds of each program (XLA module run),
  classed by the kernels it contains (``decode``, ``prefill``) or
  ``other``;
- ``gaps``: device idle seconds by what the host was doing, taken from
  the harness's own ``bench:*`` spans in the same trace; idle time
  under no span is ``loop`` (the cluster's loop between harness spans:
  arrivals, tool completions, dispatch, waiting).

Self-check on the small recorded trace kept beside this file::

    python benchmarks/chip/trace_reduce.py --check
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import sys
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

HERE = Path(__file__).resolve().parent
SAMPLE = HERE / "testdata" / "decode_sample.xplane.pb"
SAMPLE_EXPECT = HERE / "testdata" / "decode_sample.expect.json"

KERNELS = {"paged_decode": "paged_decode", "paged_prefill": "paged_prefill"}
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "traced"   # the harness's span over the whole traced window

Interval = Tuple[int, int]


def find_trace(root: str) -> str:
    files = sorted(glob.glob(f"{root}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def _text(ev) -> str:
    parts = [ev.name]
    for stat in ev.stats:
        val = stat[1] if isinstance(stat, tuple) else getattr(stat, "value", "")
        if isinstance(val, str):
            parts.append(val)
    return " ".join(parts)


CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """``%copy.39 = bf16[...] copy(...)`` -> ``copy``: the op's kind."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if tail.isdigit() and base else head


def _union(ivs: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _segments(spans: List[Tuple[int, int, str]]):
    """Cut time into pieces labelled by the innermost open span.

    Returns ``(starts, labels)``: from ``starts[i]`` on, the innermost
    span is ``labels[i]`` (``"loop"`` where none is open).
    """
    bounds = []
    for s, e, name in spans:
        bounds.append((s, 1, -(e - s), name))
        bounds.append((e, 0, 0, name))
    bounds.sort()
    starts, labels, open_ = [], [], []
    for t, is_open, neg_len, name in bounds:
        if is_open:
            open_.append((-neg_len, name))
        elif (next((x for x in open_ if x[1] == name), None)) is not None:
            open_.remove(next(x for x in open_ if x[1] == name))
        label = min(open_)[1] if open_ else "loop"
        if starts and starts[-1] == t:
            labels[-1] = label
        else:
            starts.append(t)
            labels.append(label)
    return starts, labels


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and plane_name[12:].isdigit()


def reduce_trace(path: str) -> Dict:
    """Reduce one ``.xplane.pb`` over its ``bench:traced`` span (else the
    whole trace)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = []
    spans: List[Tuple[int, int, str]] = []
    for plane in pd.planes:
        if _is_device(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns),
                                      ev.name[len(SPAN_PREFIX):]))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")

    per_dev_busy, ops, kernel_s, program_s = [], {}, {k: 0.0 for k in KERNELS}, {}
    gaps: Dict[str, float] = {}
    longest: List[Tuple[float, str]] = []
    win_lens = []
    for _name, lines in devices:
        op_evs = lines.get("XLA Ops", [])
        ivs = [(int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in op_evs]
        traced = [(s, e) for s, e, name in spans if name == WINDOW_SPAN]
        if traced:
            lo, hi = traced[0]
        else:
            all_ivs = ivs + [(s, e) for s, e, _ in spans]
            lo = min(s for s, _ in all_ivs)
            hi = max(e for _, e in all_ivs)
        win_lens.append((hi - lo) / 1e9)
        clipped = [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]
        busy = _union(clipped)
        per_dev_busy.append(sum(e - s for s, e in busy) / 1e9)
        texts = []
        for ev in op_evs:
            s = int(ev.start_ns)
            if s < lo or s >= hi:
                continue
            dur = float(ev.duration_ns) / 1e9
            kind = short_name(ev.name)
            if kind not in CONTAINERS:   # their inner ops are counted
                ops[kind] = ops.get(kind, 0.0) + dur / len(devices)
            txt = _text(ev)
            texts.append((s, txt))
            for key, sub in KERNELS.items():
                if sub in txt:
                    kernel_s[key] += dur / len(devices)
        texts.sort()
        starts = [s for s, _ in texts]
        for m in lines.get("XLA Modules", []):
            ms, me = int(m.start_ns), int(m.start_ns + m.duration_ns)
            if ms < lo or ms >= hi:
                continue
            i, j = bisect.bisect_left(starts, ms), bisect.bisect_right(starts, me)
            inside = " ".join(t for _, t in texts[i:j])
            kind = next((k for k, sub in (("decode", KERNELS["paged_decode"]),
                                          ("prefill", KERNELS["paged_prefill"]))
                         if sub in inside), "other")
            program_s[kind] = program_s.get(kind, 0.0) + (me - ms) / 1e9 / len(devices)
        # idle gaps, labelled by the innermost harness span at their middle
        seg_t, seg_label = _segments([sp for sp in spans if sp[2] != WINDOW_SPAN])
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                k = bisect.bisect_right(seg_t, (prev + s) // 2) - 1
                label = seg_label[k] if k >= 0 else "loop"
                dur = (s - prev) / 1e9
                gaps[label] = gaps.get(label, 0.0) + dur / len(devices)
                longest.append((dur, label))
            prev = max(prev, e)
    longest.sort(reverse=True)
    return {
        "devices": [n for n, _ in devices],
        "window_s": sum(win_lens) / len(win_lens),
        "busy_s": sum(per_dev_busy) / len(per_dev_busy),
        "ops": dict(sorted(ops.items(), key=lambda kv: -kv[1])),
        "kernel_s": kernel_s,
        "program_s": program_s,
        "gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1])),
        "longest_gaps": [[lab, d] for d, lab in longest[:10]],
        "n_spans": len(spans),
    }


def breakdown(red: Dict) -> Dict[str, List]:
    """The result line's ``breakdown``: top device ops and idle by span."""
    return {
        "device_ops": [[k, v] for k, v in list(red["ops"].items())[:10]],
        "idle_gaps": [[k, v] for k, v in list(red["gaps"].items())[:10]],
    }


def _check() -> int:
    red = reduce_trace(str(SAMPLE))
    want = json.loads(SAMPLE_EXPECT.read_text())
    bad = []
    for key in ("busy_s", "window_s"):
        if abs(red[key] - want[key]) > 1e-9 * max(1.0, abs(want[key])):
            bad.append(f"{key}: {red[key]} != {want[key]}")
    for key in ("kernel_s", "program_s", "gaps"):
        for k, v in want[key].items():
            if abs(red[key].get(k, 0.0) - v) > 1e-9:
                bad.append(f"{key}[{k}]: {red[key].get(k)} != {v}")
    if not 0 < red["busy_s"] <= red["window_s"]:
        bad.append("busy_s outside (0, window_s]")
    if red["kernel_s"]["paged_decode"] <= 0:
        bad.append("no decode kernel time found")
    for line in bad:
        print("trace_reduce check FAILED:", line, file=sys.stderr)
    print(json.dumps({"ok": not bad, "busy_s": red["busy_s"],
                      "window_s": red["window_s"],
                      "kernel_s": red["kernel_s"]}))
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
        red = reduce_trace(str(SAMPLE))
        SAMPLE_EXPECT.write_text(json.dumps(
            {k: red[k] for k in ("busy_s", "window_s", "kernel_s", "program_s", "gaps")},
            indent=1) + "\n")
        return 0
    path = opts.trace if opts.trace.endswith(".pb") else find_trace(opts.trace)
    print(json.dumps(reduce_trace(path), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
