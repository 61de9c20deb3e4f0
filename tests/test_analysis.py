"""Analysis-layer tests: lint framework, kvsan, scheduler invariants.

Covers the static-analysis tentpole end to end:

- **framework units** — suppression parsing (bare and ``[rule]`` forms),
  rule filtering, parse-error reporting, the rule catalog;
- **per-rule lint units** — wall-clock (``time.time``, ``datetime.now``,
  ``from time import time``), unordered-set iteration, mutable default
  arguments, and the seed-discipline rules absorbed from the retired
  ``tools/check_seeds.py`` (keyword/positional/splat seeds, unseeded
  RNG constructors, module-level global-RNG use);
- **repo sweep** — ``run_paths`` over ``src/ benchmarks/ examples/
  tests/`` returns zero findings (the repo stays suppress-free);
- **kvsan units** — double free vs refcount underflow wording,
  use-after-free and CoW-bypass writes, block-table aliasing, ticket
  refcount drift, EDF-drain violations, shadow/allocator crosscheck;
- **mutation tests** — a deliberately injected double free, a
  CoW-bypassing engine write, and a stale-plan retraction bug are each
  caught loudly by the corresponding checker;
- **observability guarantees** — a clean ``sanitize=True`` run is
  byte-identical to ``sanitize=False``, and ``check_invariants=True``
  never perturbs the LLMSched decision stream.
"""

import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analysis import (
    Source,
    all_checkers,
    check_source,
    iter_py_files,
    rule_catalog,
    run_paths,
)
from repro.analysis.invariants import InvariantViolation, check_decision
from repro.analysis.kvsan import KVSanError, KVSanitizer
from repro.configs import get_smoke_config
from repro.core import LLMSched, ProfileStore
from repro.core.dag import Task, TaskState
from repro.core.scheduler import ClusterView, Decision
from repro.kernels.paged_attention import check_block_table_bounds
from repro.models import init_params
from repro.serving import PageAllocator, PagedLLMEngine, Request
from repro.sim import generate_traces, get_generators
from repro.sim.simulator import ClusterSim
from repro.sim.workloads import generate_tiered_workload

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("stablelm_1_6b")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.key(0))[0]


_STORE = None


def _store():
    global _STORE
    if _STORE is None:
        gens = get_generators()
        apps = [g.template for g in gens.values()]
        _STORE = ProfileStore().fit(apps, generate_traces("mixed", 120, seed=7))
    return _STORE


def _sched(**kw):
    kw.setdefault("epsilon", 0.0)
    kw.setdefault("seed", 0)
    return LLMSched(_store(), **kw)


def _lint(code, rules=None):
    """Lint a dedented snippet with every registered checker."""
    src = Source("<snippet>", textwrap.dedent(code))
    return check_source(src, all_checkers(rules), rules)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# framework: suppressions, filtering, catalog, file walking
# ---------------------------------------------------------------------------
def test_rule_catalog_is_complete():
    assert set(rule_catalog()) == {
        "wall-clock", "unordered-set", "mutable-default", "builtin-hash",
        "seed-missing", "unseeded-rng", "global-rng",
        "hot-loop-import",
    }


def test_suppression_parsing_forms():
    src = Source("<s>", (
        "x = 1  # analysis: ignore\n"
        "y = 2  # analysis: ignore[wall-clock]\n"
        "z = 3  # analysis: ignore[wall-clock, seed-missing]\n"
        "w = 4\n"
    ))
    assert src.suppressed(1, "anything")
    assert src.suppressed(2, "wall-clock")
    assert not src.suppressed(2, "seed-missing")
    assert src.suppressed(3, "seed-missing")
    assert not src.suppressed(4, "wall-clock")


def test_suppression_silences_only_named_rule():
    flagged = _lint("import time\nt = time.time()\n")
    assert _rules(flagged) == ["wall-clock"]
    assert _lint(
        "import time\nt = time.time()  # analysis: ignore[wall-clock]\n"
    ) == []
    assert _lint("import time\nt = time.time()  # analysis: ignore\n") == []
    # suppressing a different rule leaves the finding live
    still = _lint(
        "import time\nt = time.time()  # analysis: ignore[seed-missing]\n"
    )
    assert _rules(still) == ["wall-clock"]


def test_rule_filtering():
    code = (
        "import time\n"
        "t = time.time()\n"
        "def f(xs=[]):\n"
        "    return xs\n"
    )
    assert _rules(_lint(code)) == ["wall-clock", "mutable-default"]
    assert _rules(_lint(code, rules={"mutable-default"})) == ["mutable-default"]


def test_parse_error_reported_not_raised(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n    pass\n")
    findings = run_paths([str(bad)])
    assert len(findings) == 1
    assert findings[0].rule == "parse-error"
    assert str(bad) in str(findings[0])


def test_iter_py_files_sorted_and_filtered(tmp_path):
    (tmp_path / "b.py").write_text("x = 1\n")
    (tmp_path / "a.py").write_text("y = 2\n")
    (tmp_path / "notes.txt").write_text("not python\n")
    sub = tmp_path / "sub"
    sub.mkdir()
    (sub / "c.py").write_text("z = 3\n")
    names = [p.name for p in iter_py_files([str(tmp_path)])]
    assert names == ["a.py", "b.py", "c.py"]


# ---------------------------------------------------------------------------
# determinism rules
# ---------------------------------------------------------------------------
def test_wall_clock_rule_positives():
    assert _rules(_lint("import time\nt = time.time()\n")) == ["wall-clock"]
    assert _rules(_lint(
        "from time import time\nt = time()\n"
    )) == ["wall-clock"]
    assert _rules(_lint(
        "import datetime\nd = datetime.datetime.now()\n"
    )) == ["wall-clock"]
    assert _rules(_lint(
        "from datetime import date\nd = date.today()\n"
    )) == ["wall-clock"]


def test_wall_clock_rule_negatives():
    assert _lint("import time\nt = time.perf_counter()\n") == []
    assert _lint("import time\nt = time.monotonic()\n") == []
    # a foreign object with a .time() method is not the time module
    assert _lint("t = sim.time()\n") == []


def test_unordered_set_rule():
    assert _rules(_lint(
        "for x in {1, 2, 3}:\n    print(x)\n"
    )) == ["unordered-set"]
    assert _rules(_lint("xs = list(set(ys))\n")) == ["unordered-set"]
    assert _rules(_lint("xs = [v for v in frozenset(ys)]\n")) == [
        "unordered-set"
    ]
    # sorted(...) fixes the order: no findings
    assert _lint("for x in sorted({1, 2, 3}):\n    print(x)\n") == []
    assert _lint("xs = sorted(set(ys))\n") == []
    # iterating an ordered container is fine
    assert _lint("for x in [1, 2]:\n    print(x)\n") == []


def test_mutable_default_rule():
    found = _lint(
        "def f(a, xs=[], *, m={}):\n"
        "    return a, xs, m\n"
    )
    assert _rules(found) == ["mutable-default", "mutable-default"]
    assert _lint("def g(a=None, b=(), c=0):\n    return a, b, c\n") == []


def test_builtin_hash_rule():
    assert _rules(_lint("k = fold_in(key, hash(name) % 7)\n")) == [
        "builtin-hash"
    ]
    assert _rules(_lint("toks = [hash(s) for s in names]\n")) == [
        "builtin-hash"
    ]
    assert _lint("import zlib\nk = zlib.crc32(name.encode())\n") == []
    # a __hash__ method serves in-process lookups only
    assert _lint(
        "class K:\n"
        "    def __hash__(self):\n"
        "        return hash((self.a, self.b))\n"
    ) == []
    # attribute calls named hash are not the builtin
    assert _lint("h = hashlib.sha256(b).hexdigest()\nx = obj.hash()\n") == []


# ---------------------------------------------------------------------------
# seed-discipline rules (parity with the retired tools/check_seeds.py)
# ---------------------------------------------------------------------------
def test_seed_missing_rule():
    assert _rules(_lint(
        'wl = generate_workload("mixed", 5)\n'
    )) == ["seed-missing"]
    assert _lint('wl = generate_workload("mixed", 5, seed=3)\n') == []
    # positional seed (4th argument) counts
    assert _lint('wl = generate_workload("mixed", 5, 1.0, 7)\n') == []
    # a **splat may carry the seed: give it the benefit of the doubt
    assert _lint('wl = generate_workload("mixed", 5, **kw)\n') == []
    assert _rules(_lint(
        'wl = generate_tiered_workload("mixed", 5, arrival_rate=1.0)\n'
    )) == ["seed-missing"]
    assert _rules(_lint('tr = generate_traces("chain", 50)\n')) == [
        "seed-missing"
    ]
    # quality gates are an RNG stream too: their per-attempt draws are
    # keyed by the gate seed, so call sites must pin it explicitly
    assert _rules(_lint(
        "g = DeterministicGate(strictness=0.7)\n"
    )) == ["seed-missing"]
    assert _lint("g = DeterministicGate(strictness=0.7, seed=3)\n") == []
    assert _lint("g = DeterministicGate(0.7, 3)\n") == []


def test_unseeded_rng_rule():
    assert _rules(_lint(
        "import numpy as np\nrng = np.random.default_rng()\n"
    )) == ["unseeded-rng"]
    assert _rules(_lint(
        "from numpy.random import default_rng\nrng = default_rng()\n"
    )) == ["unseeded-rng"]
    assert _rules(_lint(
        "import jax\nk = jax.random.key()\n"
    )) == ["unseeded-rng"]
    assert _lint("import numpy as np\nrng = np.random.default_rng(0)\n") == []
    assert _lint("import jax\nk = jax.random.key(0)\n") == []
    # a bare `key()` is ambiguous (dict.key? operator?) — never flagged
    assert _lint("k = key()\n") == []


def test_global_rng_rule():
    assert _rules(_lint(
        "import numpy as np\nx = np.random.rand(3)\n"
    )) == ["global-rng"]
    assert _rules(_lint(
        "import random\nx = random.random()\n"
    )) == ["global-rng"]
    # instance-level draws off a constructed Generator are the fix
    assert _lint("x = self.rng.random()\n") == []
    assert _lint("x = rng.choice(xs)\n") == []


# ---------------------------------------------------------------------------
# perf rules
# ---------------------------------------------------------------------------
def test_hot_loop_import_rule_positives():
    assert _rules(_lint(
        "for x in xs:\n"
        "    import json\n"
        "    json.dumps(x)\n"
    )) == ["hot-loop-import"]
    assert _rules(_lint(
        "while run:\n"
        "    from os import path\n"
    )) == ["hot-loop-import"]
    # the shipped bug shape: an import anywhere inside step()
    assert _rules(_lint(
        "class Engine:\n"
        "    def step(self):\n"
        "        if self.sanitize:\n"
        "            from .kernels import check\n"
        "            check()\n"
    )) == ["hot-loop-import"]
    # nested helper defined inside step() is still per-iteration code
    assert _rules(_lint(
        "def _step():\n"
        "    def inner():\n"
        "        import json\n"
        "        return json\n"
        "    return inner()\n"
    )) == ["hot-loop-import"]


def test_hot_loop_import_rule_negatives():
    # module level and function-top lazy imports are intentional idiom
    assert _lint("import json\n") == []
    assert _lint(
        "def build():\n"
        "    import jax\n"
        "    return jax\n"
    ) == []
    # a def inside a loop resets loop context: its body runs when called
    assert _lint(
        "for x in xs:\n"
        "    def cb():\n"
        "        import json\n"
        "        return json\n"
    ) == []


def test_paged_engine_step_has_no_imports():
    """Regression: ``PagedLLMEngine.step`` once imported the bounds
    checker per iteration; the hot path must stay import-free."""
    import ast

    tree = ast.parse(
        (REPO / "src/repro/serving/paged_engine.py").read_text()
    )
    step = next(
        node
        for node in ast.walk(tree)
        if isinstance(node, ast.FunctionDef) and node.name == "step"
    )
    imports = [
        node for node in ast.walk(step)
        if isinstance(node, (ast.Import, ast.ImportFrom))
    ]
    assert imports == [], (
        f"imports inside PagedLLMEngine.step at lines "
        f"{[i.lineno for i in imports]}"
    )


def test_repo_sweep_is_clean():
    """The whole repository lints clean with zero suppressions — the
    same sweep the CI ``analysis`` job runs."""
    paths = [str(REPO / d) for d in ("src", "benchmarks", "examples", "tests")]
    findings = run_paths(paths)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_lazy_runtime_exports():
    """`import repro.analysis` exposes the runtime layers lazily."""
    import repro.analysis as analysis

    assert analysis.KVSanitizer is KVSanitizer
    assert analysis.InvariantViolation is InvariantViolation
    with pytest.raises(AttributeError):
        analysis.does_not_exist


# ---------------------------------------------------------------------------
# kernel block-table bounds check
# ---------------------------------------------------------------------------
def test_block_table_bounds_accepts_valid_tables():
    bt = np.array([[1, 2, 3], [4, 0, 0], [0, 0, 0]])
    lens = np.array([17, 3, 0])        # covers 3, 1, 0 pages at ps=8
    check_block_table_bounds(bt, lens, num_pages=8, page_size=8)


def test_block_table_bounds_rejects_out_of_pool():
    bt = np.array([[9, 2]])
    with pytest.raises(ValueError, match="out of pool bounds"):
        check_block_table_bounds(bt, np.array([4]), num_pages=8, page_size=8)
    with pytest.raises(ValueError, match="out of pool bounds"):
        check_block_table_bounds(
            np.array([[-1, 2]]), np.array([4]), num_pages=8, page_size=8
        )


def test_block_table_bounds_rejects_trash_in_covered_range():
    # 9 valid tokens at ps=8: the decode write lands in page index 1,
    # which holds the trash page — a live token was never given KV
    bt = np.array([[5, 0]])
    with pytest.raises(ValueError):
        check_block_table_bounds(bt, np.array([9]), num_pages=8, page_size=8)


def test_block_table_bounds_rejects_short_table():
    bt = np.array([[1, 2]])
    with pytest.raises(ValueError, match="needs"):
        check_block_table_bounds(bt, np.array([25]), num_pages=8, page_size=8)


# ---------------------------------------------------------------------------
# kvsan unit behaviour (shadow state, no engine)
# ---------------------------------------------------------------------------
def test_kvsan_alloc_of_live_page():
    s = KVSanitizer(8, 4)
    s.on_alloc([1, 2], owner=0)
    with pytest.raises(KVSanError, match="non-free page"):
        s.on_alloc([2], owner=1)


def test_kvsan_double_free_vs_underflow_wording():
    s = KVSanitizer(8, 4)
    s.on_alloc([3], owner=0)
    s.on_free([3])
    with pytest.raises(KVSanError, match="double free"):
        s.on_free([3])
    s.on_alloc([4], owner=1)
    # duplicate ids within one call: more frees than live refs
    with pytest.raises(KVSanError, match="refcount underflow"):
        s.on_free([4, 4])
    # the failed call mutated nothing: the single live ref frees cleanly
    s.on_free([4])


def test_kvsan_write_checks():
    s = KVSanitizer(8, 4)
    s.on_alloc([1, 2], owner=0)
    s.note_table(0, [1, 2])
    s.note_write(0, 1)                 # exclusive, registered: fine
    assert s.writes_checked == 1
    with pytest.raises(KVSanError, match="use-after-free"):
        s.note_write(0, 5)             # page 5 is still free
    s.on_alloc([3], owner=9)
    with pytest.raises(KVSanError, match="stray write"):
        s.note_write(0, 3)             # live but not in row 0's table
    s.on_fork([2], owner=1)
    with pytest.raises(KVSanError, match="copy-on-write bypass"):
        s.note_write(0, 2)             # shared page: must CoW first
    s.on_free([2])
    s.on_mark_indexed([1])
    with pytest.raises(KVSanError, match="copy-on-write bypass"):
        s.note_write(0, 1)             # index-registered page


def test_kvsan_block_table_aliasing():
    s = KVSanitizer(8, 4)
    s.on_alloc([1], owner=0)
    s.note_table(0, [1])
    with pytest.raises(KVSanError, match="aliasing"):
        s.note_table(1, [1])           # exclusive page in two tables


def test_kvsan_ticket_drift():
    s = KVSanitizer(8, 4)
    s.on_alloc([1, 2], owner=0)
    s.on_fork([2], owner=1)
    s.validate_ticket([1, 2], [1, 2])  # matches shadow: fine
    s.validate_ticket([1, 2], None)    # legacy ticket without refcounts
    with pytest.raises(KVSanError, match="refcount drift"):
        s.validate_ticket([1, 2], [1, 1])
    with pytest.raises(KVSanError, match="refcounts"):
        s.validate_ticket([1, 2], [1])


def test_kvsan_edf_drain():
    s = KVSanitizer(8, 4)
    s.check_edf_drain(1.0, [2.0, 3.0])
    s.check_edf_drain(float("inf"), [])
    with pytest.raises(KVSanError, match="EDF violation"):
        s.check_edf_drain(5.0, [2.0])


def test_kvsan_crosscheck_divergence():
    a = PageAllocator(8, 4, sanitize=True)
    pages = a.alloc(2, owner=1)
    assert pages is not None
    a.free(pages)
    a.check_no_leaks()                 # shadow and books agree
    pages = a.alloc(1, owner=2)
    a._ref[pages[0]] += 1              # mutate behind the sanitizer's back
    with pytest.raises(KVSanError, match="divergence"):
        a.check_no_leaks()


# ---------------------------------------------------------------------------
# mutation tests: injected bugs must be caught loudly
# ---------------------------------------------------------------------------
def test_mutation_double_free_caught():
    """An injected double free dies at the free site with a journal."""
    a = PageAllocator(16, 8, sanitize=True)
    pages = a.alloc(3, owner=1)
    a.free(pages[:1])
    with pytest.raises(KVSanError, match="double free") as ei:
        a.free(pages)                  # pages[0] already returned
    assert "recent page ops" in str(ei.value)


def _run_trace(cfg, params, prompts, *, sanitize, prefix=True, n_new=6,
               max_steps=600):
    """Drive one paged engine over a staggered arrival trace."""
    eng = PagedLLMEngine(cfg, max_seqs=8, max_len=64, page_size=8,
                         params=params, prefill_chunk=8,
                         prefix_cache=prefix, sanitize=sanitize)
    out = {}
    pending = [
        Request(rid=i, prompt=list(p), max_new_tokens=n_new,
                on_finish=lambda r: out.__setitem__(r.rid, list(r.out_tokens)))
        for i, p in enumerate(prompts)
    ]
    steps = 0
    while (pending or eng.batch_size or eng.waiting) and steps < max_steps:
        if pending and steps % 2 == 0 and eng.can_admit() \
                and eng.admit(pending[0]):
            pending.pop(0)
        eng.step()
        steps += 1
    assert not pending and not eng.batch_size and not eng.waiting
    eng.allocator.check_no_leaks()
    return out, eng


def test_mutation_cow_bypass_caught(cfg, params, monkeypatch):
    """Disabling copy-on-write makes a shared-prefix trace write into a
    shared/index-registered page — the sanitizer must name it."""
    monkeypatch.setattr(
        PagedLLMEngine, "_ensure_exclusive", lambda self, row, pi: True
    )
    shared = [3 + (7 * i) % 29 for i in range(32)]   # 4 pages at ps=8
    prompts = (
        [shared + [50 + i] for i in range(4)]
        + [shared, shared]                           # aligned duplicates
    )
    with pytest.raises(KVSanError, match="copy-on-write bypass"):
        _run_trace(cfg, params, prompts, sanitize=True)


def test_mutation_stale_plan_caught(monkeypatch):
    """A scheduler that stops retracting stale SLO plans decides from
    outdated evidence — check_invariants must refuse the decision."""
    wl = generate_tiered_workload("mixed", 6, arrival_rate=0.9, seed=8)
    jobs = [gj.job for gj in wl]
    sched = _sched(check_invariants=True)
    view = ClusterView(now=0.0, free_regular=4, llm_loads=[(0, 8)])
    sched.schedule(jobs, view)         # clean round builds the plans
    assert sched._slo_plans

    orig = LLMSched._slo_plan_for

    def never_retract(self, job, v, lo, hi):
        plan = self._slo_plans.get(job.job_id)
        return plan if plan is not None else orig(self, job, v, lo, hi)

    monkeypatch.setattr(LLMSched, "_slo_plan_for", never_retract)
    for j in jobs:
        j.bump_evidence()              # new evidence: plans are now stale
    with pytest.raises(InvariantViolation, match="plan-pinned"):
        sched.schedule(jobs, view)


# ---------------------------------------------------------------------------
# observation-only guarantees
# ---------------------------------------------------------------------------
def test_sanitized_run_is_byte_identical(cfg, params):
    """A clean shared-prefix trace produces identical tokens with the
    sanitizer on and off, and the sanitizer really checked writes."""
    shared = [3 + (7 * i) % 29 for i in range(32)]
    prompts = (
        [shared + [50 + i] for i in range(4)]
        + [shared, shared]
        + [[70, 71, 72]]
    )
    base, _ = _run_trace(cfg, params, prompts, sanitize=False)
    got, eng = _run_trace(cfg, params, prompts, sanitize=True)
    assert got == base
    assert eng.allocator.sanitizer is not None
    assert eng.allocator.sanitizer.writes_checked > 0
    assert eng.prefix_index.hits > 0   # the trace exercised CoW paths


def test_invariant_checking_is_inert():
    """check_invariants=True never perturbs the decision stream on a
    clean tiered-SLO simulation (observation-only)."""
    def run(check):
        wl = generate_tiered_workload("mixed", 12, arrival_rate=1.2, seed=11)
        jid = {gj.job.job_id: i for i, gj in enumerate(wl)}
        sched = _sched(check_invariants=check)
        log = []
        orig = sched.schedule

        def rec(jobs, view):
            dec = orig(jobs, view)
            log.append((
                tuple((jid[t.job_id], t.stage_name, t.index)
                      for t in dec.regular),
                tuple((jid[t.job_id], t.stage_name, t.index)
                      for t in dec.llm),
                tuple(sorted(
                    (jid[j], s, i, e)
                    for (j, s, i), e in dec.placement.items()
                )),
            ))
            return dec

        sched.schedule = rec
        res = ClusterSim(sched, n_regular=4, n_llm=2, max_batch=8,
                         seed=0).run(wl)
        return log, round(res.avg_jct, 9)

    log_off, jct_off = run(False)
    log_on, jct_on = run(True)         # also proves: no false positives
    assert log_on == log_off
    assert jct_on == jct_off


# ---------------------------------------------------------------------------
# invariant units: each predicate fires on a crafted bad decision
# ---------------------------------------------------------------------------
def _view(loads=((0, 8),)):
    return ClusterView(now=0.0, free_regular=4, llm_loads=list(loads))


def test_invariant_no_running_retraction():
    sched = _sched()
    t = Task(job_id=1, stage_name="s", index=0, is_llm=False,
             state=TaskState.RUNNING)
    dec = Decision(regular=[t])
    with pytest.raises(InvariantViolation, match="no-running-retraction"):
        check_decision(sched, [], _view(), dec)


def test_invariant_demoted_unplaced():
    sched = _sched()
    sched._demoted = {7}
    t = Task(job_id=7, stage_name="llm", index=0, is_llm=True)
    dec = Decision(llm=[t])
    dec.place(t, 0)
    with pytest.raises(InvariantViolation, match="demoted-unplaced"):
        check_decision(sched, [], _view(), dec)


def test_invariant_placement_bounds():
    sched = _sched()
    t = Task(job_id=1, stage_name="llm", index=0, is_llm=True)
    dec = Decision(llm=[t])
    dec.place(t, 3)                    # only one replica exists
    with pytest.raises(InvariantViolation, match="placement-bounds"):
        check_decision(sched, [], _view(), dec)
    # overcommit: two placements into one free slot
    t2 = Task(job_id=2, stage_name="llm", index=0, is_llm=True)
    dec = Decision(llm=[t, t2])
    dec.place(t, 0)
    dec.place(t2, 0)
    with pytest.raises(InvariantViolation, match="overcommit"):
        check_decision(sched, [], _view([(7, 8)]), dec)


def test_invariant_edf_urgent_order():
    sched = _sched()
    sched._last_urgent_keys = [(0, 5.0, 10.0, 0.0), (0, 1.0, 3.0, 0.0)]
    with pytest.raises(InvariantViolation, match="edf-urgent-order"):
        check_decision(sched, [], _view(), Decision())


def test_invariant_violations_aggregate():
    """One bad round reports every broken property at once."""
    sched = _sched()
    sched._demoted = {7}
    sched._last_urgent_keys = [(0, 5.0, 10.0, 0.0), (0, 1.0, 3.0, 0.0)]
    t = Task(job_id=7, stage_name="llm", index=0, is_llm=True,
             state=TaskState.RUNNING)
    dec = Decision(llm=[t])
    dec.place(t, 5)
    with pytest.raises(InvariantViolation) as ei:
        check_decision(sched, [], _view(), dec)
    msg = str(ei.value)
    for name in ("no-running-retraction", "demoted-unplaced",
                 "placement-bounds", "edf-urgent-order"):
        assert name in msg


def test_kvsan_flags_nonfinite_logits():
    san = KVSanitizer(num_pages=4, page_size=2)
    san.check_logits([0, 3], [True, True])
    with pytest.raises(KVSanError, match=r"non-finite logits .*\[3\]"):
        san.check_logits([0, 3], [True, False])
