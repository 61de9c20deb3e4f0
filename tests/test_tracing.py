"""The served path's spans, cache counters and request stamps.

- ``repro.tracing``: every ``repro:`` span the program emits is in
  ``SPANS``, none names a kernel, and a short ``ServingCluster.run``
  (stablelm-smoke paged replicas, LLMSched, prefix cache, migration)
  recorded under ``jax.profiler.trace`` shows every exercised span,
  nested as the served path nests them;
- the profiler changes nothing: served tokens and scheduler decisions
  are identical with it on and off (the run's clock is a fake one that
  advances per reading, so the two runs see the same times);
- ``Request`` stamps: ``placed_at <= started_at <= finished_at``, and a
  preempted request is stamped again when it is placed again;
- ``ProfileStore.cache_counts``: hits never exceed lookups, and a second
  ``schedule`` with no new evidence and the same profile object hits in
  every cache it looks up;
- the step programs are named (``jit_decode_step``, ...).
"""

import glob
import importlib
import re
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_smoke_config
from repro.core import LLMSched, ProfileStore
from repro.core.calibration import measured_profile
from repro.core.scheduler import ClusterView
from repro.models import init_params
from repro.serving import PagedLLMEngine, Request, ServeConfig, ServingCluster
from repro.serving import cluster as cluster_mod
from repro.serving import paged_engine as paged_mod
from repro.sim import generate_traces, generate_workload, get_generators

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("stablelm_1_6b")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.key(0))[0]


@pytest.fixture(scope="module")
def store():
    apps = [g.template for g in get_generators().values()]
    return ProfileStore().fit(apps, generate_traces("mixed", 120, seed=7))


class FakeClock:
    """Stands in for ``time`` in the cluster and engine modules: each
    reading advances 1 ms and a sleep jumps, so a run's times depend on
    its work alone."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        self.t += 1e-3
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


def _serve(cfg, params, store, trace_dir=None):
    wl = generate_workload("mixed", 5, seed=11)
    for gj in wl:
        gj.job.arrival_time = 0.0
    base = min(gj.job.job_id for gj in wl)   # job ids count across runs
    engines = [
        PagedLLMEngine(cfg, max_seqs=4, max_len=64, page_size=8, num_pages=12,
                       params=params, prefill_chunk=8, prefix_cache=True)
        for _ in range(2)
    ]
    sched = LLMSched(store, epsilon=0.2, seed=0)
    decisions = []
    orig_schedule = sched.schedule

    def schedule(jobs, view):
        dec = orig_schedule(jobs, view)
        decisions.append((
            [(t.job_id - base, t.stage_name, t.index, dec.replica_for(t))
             for t in dec.llm],
            [(t.job_id - base, t.stage_name, t.index) for t in dec.regular],
        ))
        return dec

    sched.schedule = schedule
    requests, imported = [], set()
    for eng in engines:
        def admit(req, _orig=eng.admit):
            ok = _orig(req)
            if ok:
                requests.append(req)
            return ok

        def import_request(ticket, _orig=eng.import_request):
            ok = _orig(ticket)
            if ok:
                imported.add(ticket.req.rid)
            return ok

        eng.admit, eng.import_request = admit, import_request
    cluster = ServingCluster(sched, engines, ServeConfig(
        engine="paged", replicas=2, n_regular=4, token_scale=10.0,
        time_scale=50.0, shared_prompt_tokens=16, migrate=True))
    with pytest.MonkeyPatch.context() as mp:
        clock = FakeClock()
        mp.setattr(cluster_mod, "time", clock)
        mp.setattr(paged_mod, "time", clock)
        if trace_dir is None:
            res = cluster.run(wl)
        else:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(str(trace_dir), profiler_options=opts):
                res = cluster.run(wl)
    jcts = {j - base: v for j, v in res.jct_by_job.items()}
    return SimpleNamespace(res=res, decisions=decisions, requests=requests,
                           imported=imported, jcts=jcts, sched=sched)


def _spans(trace_dir):
    """``{name: [(start_ns, end_ns), ...]}`` of the trace's ``repro:`` spans."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    s = int(ev.start_ns)
                    out.setdefault(ev.name[len(tracing.PREFIX):], []).append(
                        (s, s + int(ev.duration_ns)))
    return out


@pytest.fixture(scope="module")
def traced(cfg, params, store, tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("trace")
    run = _serve(cfg, params, store, trace_dir)
    run.spans = _spans(trace_dir)
    return run


@pytest.fixture(scope="module")
def untraced(cfg, params, store):
    return _serve(cfg, params, store)


def test_span_names_in_source_are_the_listed_ones():
    used = set()
    for path in SRC.rglob("*.py"):
        used |= set(re.findall(r'\b(?:span|step)\(\s*"([^"]+)"', path.read_text()))
    assert used == set(tracing.SPANS)
    assert len(set(tracing.SPANS)) == len(tracing.SPANS)
    for name in tracing.SPANS:
        assert "paged_decode" not in name and "paged_prefill" not in name
        assert "expert_ffn" not in name


def test_traced_run_shows_every_exercised_span(traced):
    res = traced.res
    assert len(res.jcts) == 5
    # the run preempted and migrated, so re-admission and the rebalancer
    # are exercised too; only the idle sleep may not be
    assert res.preemptions > 0
    want = set(tracing.SPANS) - {"cluster.idle"}
    assert want <= set(traced.spans), sorted(want - set(traced.spans))
    assert set(traced.spans) <= set(tracing.SPANS)


@pytest.mark.parametrize("child,parents", [
    ("engine.decode.sync", ("engine.step", "loop")),
    ("engine.prefill.call", ("engine.prefill", "engine.step")),
    ("engine.prefill.sync", ("engine.prefill", "engine.step")),
    ("cluster.finish", ("engine.sample", "engine.step")),
    ("sched.ready", ("sched.schedule", "loop")),
    ("sched.bounds", ("sched.schedule",)),
    ("sched.srtf", ("sched.schedule",)),
    ("sched.uncertainty", ("sched.schedule",)),
    ("sched.merge", ("sched.schedule",)),
    ("sched.place", ("sched.schedule",)),
    ("engine.admit", ("cluster.dispatch", "loop")),
])
def test_spans_nest_as_the_served_path(traced, child, parents):
    kids = traced.spans[child]
    assert kids
    for parent in parents:
        outer = traced.spans[parent]
        for s, e in kids:
            assert any(ps <= s and e <= pe for ps, pe in outer), (child, parent)


def test_profiler_changes_no_token_and_no_decision(traced, untraced):
    assert traced.decisions == untraced.decisions
    tokens = [(r.rid, list(r.out_tokens)) for r in traced.requests]
    assert tokens == [(r.rid, list(r.out_tokens)) for r in untraced.requests]
    assert traced.jcts == untraced.jcts


def test_request_stamps_are_ordered(traced):
    assert traced.requests
    for req in traced.requests:
        assert req.done()
        if req.rid in traced.imported:
            # placed_at is the latest placement: the import, which
            # follows the first token
            assert req.started_at <= req.placed_at <= req.finished_at
        else:
            assert 0 < req.placed_at <= req.started_at <= req.finished_at


def test_preempted_request_is_stamped_again(cfg, params):
    eng = PagedLLMEngine(cfg, max_seqs=3, max_len=64, page_size=8,
                         num_pages=14, params=params)
    reqs = [Request(rid=i, prompt=[1 + i] * 4, max_new_tokens=40) for i in range(3)]
    for r in reqs:
        assert eng.admit(r)
    first = {r.rid: r.placed_at for r in reqs}
    assert all(t > 0 for t in first.values())
    for _ in range(400):
        if not (eng.batch_size or eng.waiting):
            break
        eng.step()
    assert eng.preemptions > 0 and all(r.done() for r in reqs)
    again = [r for r in reqs if r.placed_at > first[r.rid]]
    assert again, "no preempted request was placed again"
    for r in reqs:
        assert r.placed_at <= r.started_at <= r.finished_at


def test_cache_hits_never_exceed_lookups(traced):
    counts = traced.sched.profiles.cache_counts()
    assert sum(c["lookups"] for c in counts.values()) > 0
    for c in counts.values():
        assert 0 <= c["hits"] <= c["lookups"]


def test_second_schedule_with_no_new_evidence_hits(store):
    jobs = [gj.job for gj in generate_workload("mixed", 8, seed=3)]
    sched = LLMSched(store, epsilon=0.2, seed=0)
    prof = measured_profile({1: [0.01], 4: [0.02]})
    view = ClusterView.assemble(now=0.0, free_regular=4, llm_loads=[(0, 4)],
                                latency_profile=prof)
    sched.schedule(jobs, view)
    before = store.cache_counts()
    sched.schedule(jobs, view)
    after = store.cache_counts()
    delta = {c: {k: after[c][k] - before[c][k] for k in ("lookups", "hits")}
             for c in after}
    for c in ("est_remaining", "job_bounds", "uncertainty"):
        assert delta[c]["lookups"] > 0, c
    for c, d in delta.items():
        assert d["hits"] == d["lookups"], (c, d)


def _program_args(eng, program):
    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    bt = i32(eng.pages_per_seq)
    if program == "decode_step":
        return eng._decode, (eng.params, eng.pools, i32(1), i32(1, eng.pages_per_seq), i32(1))
    if program == "prefill_chunk":
        return eng._prefill_fn(0), (eng.params, eng.pools, i32(1, 8), bt)
    return eng._copy_page_jit, (eng.pools["blocks"], np.int32(0), np.int32(0))


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk", "copy_page"])
def test_step_programs_are_named(cfg, params, program):
    eng = PagedLLMEngine(cfg, max_seqs=2, max_len=32, page_size=8, params=params)
    fn, args = _program_args(eng, program)
    text = fn.lower(*args).as_text()
    assert f"@jit_{program}" in text
    assert "jit__lambda" not in text.split("\n", 1)[0]


@pytest.mark.parametrize("reducer", ["program_trace", "trace_reduce"])
def test_recorded_chip_trace_reduces_to_its_expectation(reducer, monkeypatch, capsys):
    """Both reducers' ``--check`` on their recorded chip samples, on CPU:
    ``program_trace`` on the sample with the program's spans (its idle
    by span adds up to ``trace_reduce``'s idle), ``trace_reduce`` on the
    older sample without them."""
    chip = SRC.parents[1] / "benchmarks" / "chip"
    monkeypatch.syspath_prepend(str(chip))
    mod = importlib.import_module(reducer)
    assert mod.main(["--check"]) == 0, capsys.readouterr().err
