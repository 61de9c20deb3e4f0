"""Per-architecture smoke tests (assignment requirement).

Each of the 10 assigned architectures instantiates its REDUCED config and
runs one forward + one train step + prefill/decode on CPU, asserting
output shapes and the absence of NaNs.  A decode-parity test checks that
prefill+decode_step reproduces the full-sequence forward logits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.distributed.optimizer import OptConfig
from repro.models import (
    SHAPES,
    decode_step,
    forward,
    init_params,
    lm_loss,
    prefill,
    shape_applicable,
)
from repro.models.zoo import build_train_step, input_specs
from repro.distributed.optimizer import init_opt_state


def _batch(cfg, B=2, S=16):
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab, jnp.int32)
    enc = None
    if cfg.family in ("vlm", "audio"):
        enc = jnp.full((B, cfg.encoder.n_ctx, cfg.d_model), 0.01, jnp.float32)
    return toks, enc


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nan(arch):
    cfg = get_smoke_config(arch)
    params, specs = init_params(cfg, jax.random.key(0))
    toks, enc = _batch(cfg)
    logits, _ = forward(params, cfg, toks, enc_input=enc)
    assert logits.shape == (2, 16, cfg.vocab)
    assert not np.isnan(np.asarray(logits, np.float32)).any()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nan(arch):
    cfg = get_smoke_config(arch)
    params, _ = init_params(cfg, jax.random.key(0))
    opt_cfg = OptConfig(lr=1e-3, state_dtype="float32")
    step = build_train_step(cfg, opt_cfg)
    opt_state = init_opt_state(params, opt_cfg)
    toks, enc = _batch(cfg)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    if enc is not None:
        batch["enc_input"] = enc
    new_params, new_state, metrics = jax.jit(step)(params, opt_state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert int(new_state["step"]) == 1
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(
            lambda p, q: float(jnp.abs(p.astype(jnp.float32)
                                       - q.astype(jnp.float32)).sum()),
            params, new_params,
        ),
    )
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_no_nan(arch):
    cfg = get_smoke_config(arch)
    params, _ = init_params(cfg, jax.random.key(0))
    toks, enc = _batch(cfg)
    last, cache = prefill(params, cfg, toks, max_len=48, enc_input=enc)
    assert last.shape == (2, cfg.vocab)
    lg, cache = decode_step(params, cfg, cache, jnp.argmax(last, axis=-1))
    assert lg.shape == (2, cfg.vocab)
    assert not np.isnan(np.asarray(lg, np.float32)).any()
    assert int(cache["lengths"][0]) == 17


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "deepseek_v2_lite_16b",
                                  "xlstm_350m", "whisper_tiny",
                                  "trinity_mini"])
def test_decode_parity_with_forward(arch):
    """prefill(t[:n]) + decode steps == forward(t) logits (f32 smoke)."""
    cfg = get_smoke_config(arch).with_(dtype="float32")
    params, _ = init_params(cfg, jax.random.key(1))
    B, S = 1, 12
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab, jnp.int32)
    enc = None
    if cfg.family in ("vlm", "audio"):
        enc = jnp.full((B, cfg.encoder.n_ctx, cfg.d_model), 0.01, jnp.float32)
    full_logits, _ = forward(params, cfg, toks, enc_input=enc)
    n = 8
    last, cache = prefill(params, cfg, toks[:, :n], max_len=32, enc_input=enc)
    np.testing.assert_allclose(
        np.asarray(last), np.asarray(full_logits[:, n - 1]), atol=2e-3, rtol=2e-3
    )
    # feed the TRUE next tokens and compare logits step by step
    for i in range(n, S):
        lg, cache = decode_step(params, cfg, cache, toks[:, i])
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(full_logits[:, i]), atol=2e-3, rtol=2e-3,
            err_msg=f"{arch} step {i}",
        )


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_exact_numbers(arch):
    """The FULL configs carry the exact published numbers (no allocation)."""
    cfg = get_config(arch)
    expected = {
        "stablelm_1_6b": (24, 2048, 32, 32, 5632, 100352),
        "internlm2_20b": (48, 6144, 48, 8, 16384, 92544),
        "qwen1_5_110b": (80, 8192, 64, 8, 49152, 152064),
        "llama3_405b": (126, 16384, 128, 8, 53248, 128256),
        "llama3_2_vision_90b": (100, 8192, 64, 8, 28672, 128256),
        "jamba_1_5_large_398b": (72, 8192, 64, 8, 24576, 65536),
        "whisper_tiny": (4, 384, 6, 6, 1536, 51865),
        "kimi_k2_1t_a32b": (61, 7168, 64, 8, 2048, 163840),
        "deepseek_v2_lite_16b": (27, 2048, 16, 16, 1408, 102400),
        "xlstm_350m": (24, 1024, 4, 4, 0, 50304),
        "trinity_mini": (32, 2048, 32, 4, 6144, 200192),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expected


def test_param_counts_match_published():
    tol = {
        "stablelm_1_6b": (1.6e9, 0.15), "internlm2_20b": (20e9, 0.1),
        "qwen1_5_110b": (111e9, 0.1), "llama3_405b": (405e9, 0.05),
        "llama3_2_vision_90b": (90e9, 0.1),
        "jamba_1_5_large_398b": (398e9, 0.1), "whisper_tiny": (39e6, 2.0),
        "kimi_k2_1t_a32b": (1.04e12, 0.1),
        "deepseek_v2_lite_16b": (15.7e9, 0.1), "xlstm_350m": (350e6, 0.5),
        "trinity_mini": (26e9, 0.05),
    }
    for arch, (target, rel) in tol.items():
        total, active = get_config(arch).param_count()
        assert abs(total - target) / target <= rel, (arch, total)
        assert active <= total


def test_moe_active_params():
    kimi = get_config("kimi_k2_1t_a32b")
    total, active = kimi.param_count()
    assert active < 0.05 * total  # a32b of 1t
    ds = get_config("deepseek_v2_lite_16b")
    t2, a2 = ds.param_count()
    assert a2 < 0.3 * t2


def test_input_specs_cover_all_cells():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            ok, why = shape_applicable(cfg, shape)
            if not ok:
                assert shape.name == "long_500k" and not cfg.sub_quadratic
                continue
            specs = input_specs(cfg, shape)
            assert "tokens" in specs
            if shape.kind == "decode":
                assert "cache" in specs


def test_params_identical_across_hash_seeds():
    # weights derive from the seed alone: str hashes are salted per
    # process, so a name-keyed sub-seed must not come from hash()
    import hashlib
    import os
    import subprocess
    import sys

    code = (
        "import hashlib, jax, numpy as np\n"
        "from repro.configs import get_smoke_config\n"
        "from repro.models import init_params\n"
        "p = init_params(get_smoke_config('stablelm_1_6b'), jax.random.key(0))[0]\n"
        "h = hashlib.sha256()\n"
        "for leaf in jax.tree.leaves(p):\n"
        "    h.update(np.asarray(leaf, np.float32).tobytes())\n"
        "print(h.hexdigest())\n"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    sums = []
    for hash_seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hash_seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=src)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=300, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        sums.append(r.stdout.strip().splitlines()[-1])
    assert sums[0] == sums[1]
    assert hashlib.sha256(b"").hexdigest() not in sums
