"""The harness's checks, driven on the CPU at a size a test run holds.

Skips the look for a chip and runs the rest of a cell: a smoke-size
model (2 layers, width 64) through the same serve path, window, sample
and float32 reference.  A clean run must come out correct; a run whose
engine alters each token where it is produced must not; the fp8 control
put in the program's place (``--control``) must not either.  A steady
window (open loop, then the drain) runs through the same path and its
job-time readers.  Run by hand::

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import copy
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness as H  # noqa: E402
import run as RUN  # noqa: E402
import traffic as T  # noqa: E402

CELL = "stablelm.chain-short.batch"


def smoke_spec():
    spec = copy.deepcopy(H.load_config("stablelm-1.6b"))
    spec.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=4, head_dim=16, intermediate_size=128,
                vocab_size=256, torch_dtype="bfloat16")
    spec["fleet"].update(max_batch=4, max_len=128, pool_pages=1 + 4 * 8)
    return spec


def smoke_traffic(kind="batch"):
    traffic = H.load_traffic("chain-short.batch")
    if kind == "steady":
        traffic.update(window="steady",
                       arrival={"kind": "poisson", "rate_jobs_per_s": 1.0})
    else:
        traffic["arrival"] = {"kind": "batch", "jobs": 12}
    return traffic


def run_smoke(seed=123, fault=None, control=False, limit=None, kind="batch",
              spec=None, block=None):
    """One window of the cell at smoke size; ``spec`` defaults to the
    stablelm smoke configuration, ``block`` to the one it names."""
    import jax

    spec = spec or smoke_spec()
    block = block or H.load_block(spec)

    bench = H.load_benchmark()
    limits = {"max_logit_gap": {"limit": limit if limit is not None
                                else H.load_limits(CELL)["max_logit_gap"]["limit"]}}
    opts = SimpleNamespace(workload=CELL, seed=seed, seconds=4.0, trace=0,
                           control=control, keep_trace=False, bench=bench,
                           peaks={"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0})
    RUN.T_PROC = __import__("time").perf_counter()
    return RUN.run_cell(opts, spec, block, smoke_traffic(kind), limits,
                        jax.devices()[:1], on_engines=fault)


def alter_tokens(engines):
    """Fault: every decoded token is replaced by its neighbour id."""
    import jax.numpy as jnp

    for eng in engines:
        orig = eng._decode

        def decode(p, pools, toks, bt, lens, orig=orig):
            logits, pools = orig(p, pools, toks, bt, lens)
            return jnp.roll(logits, 1, axis=-1), pools
        eng._decode = decode


@pytest.fixture(scope="module")
def clean():
    return run_smoke()


def test_clean_run_is_correct(clean):
    assert clean["correct"], clean["checks"]
    assert clean["checks"]["sampled_tokens"]["value"] > 50
    assert clean["checks"]["order_violations"]["value"] == 0
    assert set(clean["metrics"]) == {"tokens_per_s", "setup_s"}
    assert clean["info"]["out_tokens"] > 0


def test_fp8_control_in_the_programs_place_is_not_correct(clean):
    limit = max(1e-3, 2 * clean["checks"]["max_logit_gap"]["value"])
    out = run_smoke(control=True, limit=limit)
    control = out["checks"]["max_logit_gap"]["value"]
    assert not out["correct"]
    assert control > limit
    assert control > out["info"]["program_max_gap"]


def test_altered_token_is_not_correct(clean):
    limit = max(1e-3, 2 * clean["checks"]["max_logit_gap"]["value"])
    out = run_smoke(fault=alter_tokens, limit=limit)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > limit


def test_steady_window_reads_job_times():
    out = run_smoke(kind="steady", limit=1e9)
    run = RUN.run_record(SimpleNamespace(**{
        "jcts": [1.0], "failed": 0, "ttft_s": [0.5], "lat_samples": [],
        "decode_rows": [], "prefill_run": 0, "prefill_skipped": 0,
        "out_tokens": 0, "sched_s": 0.0, "t_close": 1.0}),
        "steady", 4.0, 0.0, None, [])
    assert H.load_reader("jct_mean_s")(run) == 1.0
    assert H.load_reader("ttft_p95_ms")(run) == 500.0
    assert out["failed"] == 0
    assert out["info"]["jobs_finished"] == out["attempted"]


def test_changed_generators_stop_the_run():
    traffic = smoke_traffic()
    traffic["pool_seed"] = 1
    with pytest.raises(ValueError, match="digest"):
        T.make_jobs(traffic, 5, 4.0)
