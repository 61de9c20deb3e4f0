"""Serving engine + testbed runtime tests."""

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import FCFS, LLMSched, ProfileStore
from repro.serving import LLMEngine, Request, ServeConfig, ServingCluster
from repro.sim import generate_traces, generate_workload, get_generators


@pytest.fixture(scope="module")
def engine_cfg():
    return get_smoke_config("stablelm_1_6b")


def test_engine_continuous_batching(engine_cfg):
    eng = LLMEngine(engine_cfg, max_batch=4, max_len=64)
    done = []
    for i in range(4):
        assert eng.admit(Request(rid=i, prompt=[1, 2, 3], max_new_tokens=3 + i,
                                 on_finish=lambda r: done.append(r.rid)))
    assert not eng.can_admit()
    steps = 0
    while eng.batch_size and steps < 50:
        eng.step()
        steps += 1
    assert sorted(done) == [0, 1, 2, 3]
    # all tokens produced
    assert steps < 50


def test_engine_admission_midstream(engine_cfg):
    """New requests join between decode steps (iteration-level batching)."""
    eng = LLMEngine(engine_cfg, max_batch=2, max_len=64)
    done = []
    eng.admit(Request(rid=0, prompt=[1], max_new_tokens=6,
                      on_finish=lambda r: done.append(r.rid)))
    eng.step()
    eng.admit(Request(rid=1, prompt=[2], max_new_tokens=2,
                      on_finish=lambda r: done.append(r.rid)))
    steps = 0
    while eng.batch_size and steps < 30:
        eng.step()
        steps += 1
    assert sorted(done) == [0, 1]
    assert done[0] == 1  # the short request finished first


def test_engine_latency_profile(engine_cfg):
    eng = LLMEngine(engine_cfg, max_batch=4, max_len=64)
    for i in range(3):
        eng.admit(Request(rid=i, prompt=[1, 2], max_new_tokens=6))
    while eng.batch_size:
        eng.step()
    prof = eng.latency_profile()
    assert prof is not None
    assert prof.l(1) > 0
    # Eq. 2 calibration is usable
    assert prof.calibrate(10.0, b_r=1, b_t=3) > 0


def test_testbed_cluster_completes_jobs(engine_cfg):
    gens = get_generators()
    apps = [g.template for g in gens.values()]
    store = ProfileStore().fit(apps, generate_traces("chain", 150, seed=7))
    wl = generate_workload("chain", 6, arrival_rate=2.0, seed=4)
    cluster = ServingCluster(
        LLMSched(store, epsilon=0.2, seed=0),
        [LLMEngine(engine_cfg, max_batch=4, max_len=96)],
        ServeConfig(n_regular=3, token_scale=30.0, time_scale=30.0),
    )
    res = cluster.run(wl)
    assert len(res.jcts) == 6
    assert res.tokens_generated > 0
    # wall-clock dependent: generous margin for loaded CI runners (the
    # steady-state rounds are single-digit ms; the mean is dominated by
    # the first cold-cache rounds)
    assert res.avg_overhead_ms < 150


# ---------------------------------------------------------------------------
# the serve command line
# ---------------------------------------------------------------------------
def test_serve_command_line_smoke_run():
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--smoke", "--engine", "paged", "--mix", "planning", "--jobs", "3",
        "--max-len", "64", "--prefix-cache", "--shared-prompt", "32",
    ])
    assert serve.config_from_args(args).max_len == 64
    wl = serve.workload_from_args(args)
    res = serve.serve(args, workload=wl)
    assert len(res.jcts) == len(wl) == 3
    assert all(gj.job.done() for gj in wl)
    assert res.tokens_generated > 0 and res.prefill_saved_tokens > 0


def test_serve_command_line_picks_the_model():
    from repro.launch import serve

    parse = serve.build_parser().parse_args
    assert serve.model_config(parse([])).d_model == 2048       # published
    assert serve.model_config(parse(["--smoke"])).d_model == 64
    assert serve.model_config(parse(["--smoke", "--models", "a,b",
                                     "--replicas", "2"])) is None
    with pytest.raises(SystemExit, match="--smoke"):
        serve.model_config(parse(["--models", "a,b", "--replicas", "2"]))


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_directory(tmp_path, env_dir):
    import os
    import subprocess
    import sys

    from repro.launch.serve import REPO_ROOT

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO_ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = (
        "import jax\n"
        "from repro.launch.serve import enable_compile_cache\n"
        "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir,\n"
        "      jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    want = str(tmp_path) if env_dir else str(REPO_ROOT / ".jax_cache")
    assert r.stdout.split() == [want, want, "0.0"]
