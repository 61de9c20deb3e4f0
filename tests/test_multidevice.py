"""Multi-device SPMD correctness (8 forced host devices, subprocess).

These run the REAL sharded paths — EP MoE, weight-stationary decode MLP,
sharded decode — on an 8-device host mesh and check numerics against the
single-device oracle.  Subprocesses are used because the device count is
locked at jax init.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=420, env=env,
    )
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_moe_ep_matches_dense_on_mesh():
    out = _run("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_smoke_config
        from repro.models import moe as M
        from repro.models.layers import Maker
        from repro.distributed.sharding import use_mesh

        cfg = get_smoke_config("kimi_k2_1t_a32b")
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        mk = Maker(jax.random.key(0), jnp.float32)
        M.init_moe(mk, cfg.with_(dtype="float32"))
        x = jax.random.normal(jax.random.key(1), (8, 16, cfg.d_model)) * 0.5
        ref = M.moe_dense(mk.params, cfg, x)
        cfg2 = cfg.with_(moe=dataclasses.replace(cfg.moe, impl="ep",
                                                 capacity_factor=2.0))
        with use_mesh(mesh):
            out = jax.jit(lambda p, x: M.moe_ep(p, cfg2, x, mesh=mesh))(mk.params, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        print("EP-OK", err)
    """)
    assert "EP-OK" in out


@pytest.mark.slow
def test_sharded_decode_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import init_params, prefill, decode_step
        from repro.models.zoo import param_shapes
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh

        cfg = get_smoke_config("internlm2_20b").with_(dtype="float32")
        params, specs = init_params(cfg, jax.random.key(0))
        toks = jnp.asarray(np.arange(2 * 8).reshape(2, 8) % cfg.vocab, jnp.int32)

        # single device reference
        last_ref, cache = prefill(params, cfg, toks, max_len=32)
        lg_ref, _ = decode_step(params, cfg, cache, jnp.argmax(last_ref, -1))

        # sharded over (data=4, model=2)
        mesh = make_mesh((4, 2), ("data", "model"))
        with shd.use_mesh(mesh):
            p_sh = shd.tree_shardings(specs, params, mesh)
            params_s = jax.device_put(params, p_sh)
            last_s, cache_s = jax.jit(
                lambda p, t: prefill(p, cfg, t, max_len=32)
            )(params_s, toks)
            lg_s, _ = jax.jit(
                lambda p, c, t: decode_step(p, cfg, c, t)
            )(params_s, cache_s, jnp.argmax(last_s, -1))
        err = float(jnp.max(jnp.abs(lg_s - lg_ref)))
        assert err < 2e-3, err
        print("SHARD-OK", err)
    """)
    assert "SHARD-OK" in out


@pytest.mark.slow
def test_weight_stationary_decode_mlp_matches():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models.layers import Maker, init_mlp, mlp, mlp_ws_decode
        from repro.distributed.sharding import use_mesh

        cfg = get_smoke_config("llama3_405b").with_(dtype="float32")
        mk = Maker(jax.random.key(0), jnp.float32)
        init_mlp(mk, cfg.d_model, 192)
        x = jax.random.normal(jax.random.key(1), (4, 1, cfg.d_model))
        ref = mlp(mk.params, x)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        with use_mesh(mesh):
            out = jax.jit(lambda p, x: mlp_ws_decode(p, cfg, x))(mk.params, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-4, err
        print("WS-OK", err)
    """)
    assert "WS-OK" in out


@pytest.mark.slow
def test_compressed_grad_allreduce_on_mesh():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.compression import compressed_grad_allreduce

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(16, 32)),
                              jnp.float32)}
        out = compressed_grad_allreduce(g, mesh, axis="pod")
        # replicated input: psum over 2 pods = 2x the (quantized) value
        rel = float(jnp.max(jnp.abs(out["w"] - 2 * g["w"]))
                    / jnp.max(jnp.abs(g["w"])))
        assert rel < 0.05, rel
        print("COMP-OK", rel)
    """)
    assert "COMP-OK" in out


@pytest.mark.slow
def test_replicas_on_own_devices_migrate_across():
    out = _run("""
        import jax
        from repro.configs import get_smoke_config
        from repro.serving import Request, ServeConfig, build_engines

        cfg = get_smoke_config("stablelm_1_6b")
        engines = build_engines(cfg, ServeConfig(
            engine="paged", replicas=4, max_batch=2, max_len=64))
        devs = jax.devices()
        for i, eng in enumerate(engines):
            held = {d for x in jax.tree.leaves((eng.params, eng.pools))
                    for d in x.devices()}
            assert held == {devs[i]}, (i, held)

        def drive(eng, req, n):
            while len(req.out_tokens) < n and not req.done():
                eng.step()

        src, dst = engines[0], engines[1]
        alone = Request(rid=1, prompt=[3, 5], max_new_tokens=12)
        moved = Request(rid=2, prompt=[3, 5], max_new_tokens=12)
        assert src.admit(alone)
        drive(src, alone, 12)
        assert src.admit(moved)
        drive(src, moved, 4)
        assert dst.import_request(src.export_request(src.youngest_active_row()))
        drive(dst, moved, 12)
        assert moved.out_tokens == alone.out_tokens, (moved.out_tokens, alone.out_tokens)
        assert {d for x in jax.tree.leaves(dst.pools) for d in x.devices()} == {devs[1]}
        src.allocator.check_no_leaks()
        dst.allocator.check_no_leaks()
        print("PLACE-OK", dst.decoded_tokens)
    """)
    assert "PLACE-OK" in out
