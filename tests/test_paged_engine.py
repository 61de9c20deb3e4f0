"""Paged KV-cache engine + allocator tests.

Covers the edge cases the slot-engine suite never exercised: page
alloc/free invariants, admission refusal on pool exhaustion,
preemption-by-eviction with requeue, stop-token early exit, chunked
prefill, defrag — plus the acceptance gate: the paged engine matches
the slot engine token-for-token under greedy decoding.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serving import LLMEngine, PagedLLMEngine, PageAllocator, Request


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("stablelm_1_6b")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.key(0))[0]


def _drain(eng, max_steps=400):
    steps = 0
    while (eng.batch_size or getattr(eng, "waiting", ())) and steps < max_steps:
        eng.step()
        steps += 1
    return steps


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------
def test_allocator_alloc_free_invariants():
    a = PageAllocator(num_pages=8, page_size=16)
    assert a.free_pages == 7  # page 0 reserved
    p1 = a.alloc(3, owner=1)
    p2 = a.alloc(2, owner=2)
    assert p1 is not None and p2 is not None
    assert 0 not in p1 + p2                      # trash page never handed out
    assert len(set(p1) | set(p2)) == 5           # no aliasing
    assert a.alloc(3) is None                    # atomic refusal (2 left)
    assert a.free_pages == 2
    a.free(p1)
    assert a.free_pages == 5
    with pytest.raises(ValueError):
        a.free(p1)                               # double free detected
    with pytest.raises(AssertionError):
        a.check_no_leaks()                       # p2 still held
    a.free(p2)
    a.check_no_leaks()
    assert a.pages_for(0) == 0 and a.pages_for(1) == 1 and a.pages_for(17) == 2


def test_allocator_defrag_compacts():
    a = PageAllocator(num_pages=16, page_size=8)
    p1 = a.alloc(4, owner=1)
    p2 = a.alloc(4, owner=2)
    p3 = a.alloc(4, owner=3)
    a.free(p2)  # hole in the middle
    mapping = a.defrag()
    assert a.owned_by(1) + a.owned_by(3) == list(range(1, 9))  # compact
    assert all(old > new for old, new in mapping.items())
    # allocator still functional after compaction
    p4 = a.alloc(7, owner=4)
    assert p4 is not None and len(set(p4)) == 7
    a.free(p4)
    a.free([mapping.get(p, p) for p in p1])
    a.free([mapping.get(p, p) for p in p3])
    a.check_no_leaks()


# ---------------------------------------------------------------------------
# acceptance: token-for-token parity with the slot engine
# ---------------------------------------------------------------------------
def test_paged_matches_slot_token_for_token(cfg, params):
    slot = LLMEngine(cfg, max_batch=4, max_len=64, params=params)
    # pinned fp32: slot-parity is a *bit-identical* contract, which int8
    # quantization intentionally relaxes (nightly runs REPRO_KV_DTYPE=int8)
    paged = PagedLLMEngine(cfg, max_seqs=4, max_len=64, page_size=8,
                           params=params, kv_dtype="fp32")
    prompts = [[1, 2, 3], [5, 6], [7, 8, 9, 10], [2]]
    out_slot, out_paged = {}, {}
    for i, p in enumerate(prompts):
        assert slot.admit(Request(
            rid=i, prompt=p, max_new_tokens=10,
            on_finish=lambda r: out_slot.__setitem__(r.rid, list(r.out_tokens))))
        assert paged.admit(Request(
            rid=i, prompt=p, max_new_tokens=10,
            on_finish=lambda r: out_paged.__setitem__(r.rid, list(r.out_tokens))))
    _drain(slot)
    _drain(paged)
    assert out_slot == out_paged          # greedy decode: exact match
    paged.allocator.check_no_leaks()      # all pages returned


def test_chunked_prefill_interleaves_and_matches(cfg, params):
    """A prompt longer than prefill_chunk crosses chunk+page boundaries
    and still reproduces the slot engine's tokens."""
    prompt = list(range(1, 30))
    slot = LLMEngine(cfg, max_batch=2, max_len=64, params=params)
    paged = PagedLLMEngine(cfg, max_seqs=2, max_len=64, page_size=8,
                           params=params, prefill_chunk=8, kv_dtype="fp32")
    o1, o2 = {}, {}
    slot.admit(Request(rid=0, prompt=prompt, max_new_tokens=6,
                       on_finish=lambda r: o1.__setitem__(r.rid, list(r.out_tokens))))
    paged.admit(Request(rid=0, prompt=prompt, max_new_tokens=6,
                        on_finish=lambda r: o2.__setitem__(r.rid, list(r.out_tokens))))
    _drain(slot)
    # chunked prefill: the request must NOT be decoding after one step
    paged.step()
    assert paged.prefilling and not paged.active
    _drain(paged)
    assert o1 == o2


# ---------------------------------------------------------------------------
# edge cases the slot-engine suite misses
# ---------------------------------------------------------------------------
def test_admission_refused_when_pool_exhausted(cfg, params):
    eng = PagedLLMEngine(cfg, max_seqs=8, max_len=64, page_size=8,
                         num_pages=9, params=params)
    assert eng.admit(Request(rid=0, prompt=[1] * 40, max_new_tokens=4))  # 6 pages
    assert not eng.admit(Request(rid=1, prompt=[1] * 40, max_new_tokens=4))
    done = []
    assert eng.admit(Request(rid=2, prompt=[2], max_new_tokens=2,
                             on_finish=lambda r: done.append(r.rid)))
    _drain(eng)
    assert done == [2]
    eng.allocator.check_no_leaks()


def test_preemption_eviction_requeues_and_completes(cfg, params):
    """Pool too small for 3 full sequences: decode growth must evict the
    youngest (pages freed, request requeued) and still finish everyone."""
    eng = PagedLLMEngine(cfg, max_seqs=3, max_len=64, page_size=8,
                         num_pages=14, params=params)
    done = []
    for i in range(3):
        assert eng.admit(Request(rid=i, prompt=[1 + i] * 4, max_new_tokens=40,
                                 on_finish=lambda r: done.append(r.rid)))
    _drain(eng)
    assert sorted(done) == [0, 1, 2]      # evicted requests re-ran to completion
    assert eng.preemptions > 0            # eviction actually happened
    eng.allocator.check_no_leaks()        # freed victim pages were not lost


def test_no_mutual_eviction_livelock(cfg, params):
    """Two requests that each need (almost) the whole pool must not evict
    each other forever: eviction is strictly age-ordered, so the older
    one runs to completion while the younger self-preempts and waits."""
    eng = PagedLLMEngine(cfg, max_seqs=2, max_len=16, page_size=4,
                         num_pages=5, params=params)
    done = []
    for i in range(2):
        assert eng.admit(Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=11,
                                 on_finish=lambda r: done.append(r.rid)))
    steps = _drain(eng, max_steps=120)
    assert sorted(done) == [0, 1], f"livelock: {len(done)} finished in {steps} steps"
    eng.allocator.check_no_leaks()


def test_admit_refusal_with_can_admit_true(cfg, params):
    """can_admit() is a cheap 1-page pre-filter; admit() may still refuse
    a multi-page prompt.  Callers must handle the False return (the
    cluster leaves the task PENDING and retries next round)."""
    eng = PagedLLMEngine(cfg, max_seqs=4, max_len=16, page_size=2,
                         num_pages=9, params=params)
    assert eng.admit(Request(rid=0, prompt=[1] * 13, max_new_tokens=2))  # 7 pages
    assert eng.can_admit()                      # 1 page free, row free
    assert not eng.admit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=2))
    # the refusal left no partial state behind
    assert len(eng.seq_pages) == 1 and len(eng.free_rows) == 3
    _drain(eng)
    eng.allocator.check_no_leaks()


def test_stop_token_early_exit(cfg, params):
    ref = PagedLLMEngine(cfg, max_seqs=1, max_len=64, page_size=8, params=params)
    outs = {}
    ref.admit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=12,
                      on_finish=lambda r: outs.__setitem__(r.rid, list(r.out_tokens))))
    _drain(ref)
    seq = outs[0]
    stop = seq[3]                          # a token generated mid-stream
    eng = PagedLLMEngine(cfg, max_seqs=1, max_len=64, page_size=8, params=params)
    outs2 = {}
    eng.admit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=12, stop_token=stop,
                      on_finish=lambda r: outs2.__setitem__(r.rid, list(r.out_tokens))))
    _drain(eng)
    got = outs2[0]
    first_stop = next(i for i, t in enumerate(got) if i >= 1 and t == stop)
    assert got[-1] == stop and len(got) == first_stop + 1
    assert len(got) < len(seq)            # actually exited early
    eng.allocator.check_no_leaks()


def test_engine_defrag_after_churn(cfg, params):
    """Finish interleaved requests to fragment the pool, defrag, and keep
    decoding — remapped pages must preserve outputs exactly."""
    def run(defrag_at):
        eng = PagedLLMEngine(cfg, max_seqs=4, max_len=64, page_size=8,
                             params=params)
        outs = {}
        lens = [3, 14, 3, 14]
        for i, n in enumerate(lens):
            eng.admit(Request(rid=i, prompt=[2 + i, 5], max_new_tokens=n,
                              on_finish=lambda r: outs.__setitem__(r.rid, list(r.out_tokens))))
        steps = 0
        moved = 0
        while eng.batch_size and steps < 100:
            eng.step()
            steps += 1
            if steps == defrag_at:
                moved = eng.defrag()
        eng.allocator.check_no_leaks()
        return outs, moved

    base, _ = run(defrag_at=-1)
    # short requests finish by step 5 -> their pages leave holes
    got, moved = run(defrag_at=6)
    assert got == base
    assert moved > 0                       # compaction actually moved pages


def test_latency_profile_feeds_calibration(cfg, params):
    eng = PagedLLMEngine(cfg, max_seqs=4, max_len=64, page_size=8, params=params)
    for i in range(3):
        eng.admit(Request(rid=i, prompt=[1, 2], max_new_tokens=6))
    _drain(eng)
    prof = eng.latency_profile()
    assert prof is not None and prof.l(1) > 0
    assert prof.calibrate(10.0, b_r=1, b_t=3) > 0


# ---------------------------------------------------------------------------
# EDF waiting-queue drain: equal deadlines re-admit in arrival order
# ---------------------------------------------------------------------------
def test_equal_deadline_waiters_drain_in_arrival_order(cfg, params):
    """Regression: the waiting-queue drain tie-broke equal-priority
    requests by deque position (= eviction order), not arrival order.
    Single-engine eviction happens to preserve arrival order, but a
    live-migrated request evicted late sits at the deque head — so an
    equal-deadline *younger* arrival was re-admitted ahead of an older
    waiter.  The drain key is now (priority, arrival_seq)."""
    e1 = PagedLLMEngine(cfg, max_seqs=2, max_len=64, page_size=8, params=params)
    e2 = PagedLLMEngine(cfg, max_seqs=2, max_len=64, page_size=8, params=params)

    # A arrives first (on e2), B second (on e1): fleet arrival order A < B
    req_a = Request(rid=1, prompt=[1, 2, 3], max_new_tokens=20, priority=5.0)
    req_b = Request(rid=2, prompt=[4, 5, 6], max_new_tokens=20, priority=5.0)
    assert e2.admit(req_a)
    assert e1.admit(req_b)
    assert req_a.arrival_seq < req_b.arrival_seq

    # migrate A onto e1: it lands with the *youngest* admission stamp
    # there despite being the older arrival
    for _ in range(4):           # finish A's prefill so it is exportable
        e2.step()
    row_a = e2.youngest_active_row()
    assert row_a is not None
    assert e1.import_request(e2.export_request(row_a))

    # pool pressure evicts youngest-row first (exactly what _evict_for
    # does when decode growth finds the pool dry): A is evicted before
    # B, so appendleft leaves the younger arrival B at the deque head
    for _ in range(2):
        rows = dict(e1.active)
        rows.update({r: rq for r, (rq, _) in e1.prefilling.items()})
        e1._evict_row(max(rows, key=lambda r: e1._row_seq[r]))
    assert list(e1.waiting)[0] is req_b   # the head-position trap

    # drain: the older arrival must re-admit first despite B at head
    e1.step()
    rows = dict(e1.active)
    rows.update({r: rq for r, (rq, _) in e1.prefilling.items()})
    seq_of = {rq.rid: e1._row_seq[r] for r, rq in rows.items()}
    assert seq_of[req_a.rid] < seq_of[req_b.rid], (
        "equal-deadline drain re-admitted the younger arrival first"
    )


# ---------------------------------------------------------------------------
# warm-up and device placement
# ---------------------------------------------------------------------------
def test_warm_up_touches_no_request_state(cfg, params):
    prompts = [[1 + (3 * i + k) % 50 for k in range(18)] for i in range(3)]
    prompts.append(list(prompts[0][:16]))  # page-aligned full hit: CoW

    def serve(warm):
        eng = PagedLLMEngine(cfg, max_seqs=4, max_len=48, page_size=8,
                             params=params, prefix_cache=True)
        if warm:
            eng.warm_up([18, 16], cached_tokens=(0, 16))
            assert eng.allocator.free_pages == eng.num_pages - 1
            assert eng.prefix_cached_tokens == 0
            assert (eng.cow_copies, eng.decoded_tokens) == (0, 0)
            assert eng.latency_profile() is None
        out = []
        for i, p in enumerate(prompts):
            req = Request(rid=i, prompt=p, max_new_tokens=6)
            assert eng.admit(req)
            _drain(eng)
            out.append(req.out_tokens)
        eng.allocator.check_no_leaks(allow_indexed=True)
        return out, eng.cow_copies

    warm = serve(warm=True)
    assert warm == serve(warm=False)
    assert warm[1] == 1   # the page-aligned hit copied on write


def test_engine_state_lives_on_its_device(cfg, params):
    dev = jax.devices()[-1]
    eng = PagedLLMEngine(cfg, max_seqs=2, max_len=32, page_size=8,
                         params=params, device=dev)
    leaves = jax.tree.leaves((eng.params, eng.pools))
    assert {d for x in leaves for d in x.devices()} == {dev}
    req = Request(rid=0, prompt=[3, 4, 5], max_new_tokens=4)
    assert eng.admit(req)
    _drain(eng)
    assert len(req.out_tokens) == 4 and eng.decoded_tokens == 3
    assert {d for x in jax.tree.leaves(eng.pools) for d in x.devices()} == {dev}
