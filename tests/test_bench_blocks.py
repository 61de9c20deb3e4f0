"""The chip benchmark's block modules against the program, on the CPU.

- ``blocks/dense.py`` counts the recorded work of both dense
  configurations and makes the recorded weights and reference logits,
  bit for bit (the numbers and sha256 digests of
  ``benchmarks/chip/tests/test_blocks.py``);
- ``blocks/afmoe.py``'s float32 ``forward`` equals the program's
  ``forward`` at a smoke size of ``trinity-mini.ep8`` on the same
  weights, and its work counts stop sliding layers at the window and
  count only the held experts;
- the harness's tree check passes for ``trinity-mini.ep8`` at its
  published widths (traced abstractly: nothing is made).
"""

import copy
import hashlib
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


@pytest.fixture
def H(monkeypatch):
    """The benchmark's ``harness`` module, with ``benchmarks/chip`` on the path."""
    monkeypatch.syspath_prepend(str(CHIP))
    import harness

    return harness


def _recorded():
    """``WORK``, ``SMOKE`` and ``DIGESTS`` as the benchmark's own tests hold them
    (the caller has ``benchmarks/chip/tests`` on the path, for its imports)."""
    path = CHIP / "tests" / "test_blocks.py"
    spec = importlib.util.spec_from_file_location("bench_test_blocks", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ["stablelm-1.6b", "internlm2-20b.8l"])
def test_dense_block_counts_the_recorded_work(H, name, monkeypatch):
    monkeypatch.syspath_prepend(str(CHIP / "tests"))
    work = _recorded().WORK[name]
    spec = H.load_config(name)
    block = H.load_block(spec)
    assert block.decode_step_flops(spec, [100] * 32) == work[0]
    assert block.attention_work(spec, [512, 1024]) == work[1]
    assert block.prefill_chunk_flops(spec, 448, 64) == work[2]


@pytest.mark.parametrize("name", ["stablelm-1.6b", "internlm2-20b.8l"])
def test_dense_block_makes_the_recorded_weights_and_logits(H, name, monkeypatch):
    import reference as R

    monkeypatch.syspath_prepend(str(CHIP / "tests"))
    rec = _recorded()
    spec = copy.deepcopy(H.load_config(name))
    spec.update(rec.SMOKE[name])
    cfg = H.model_config(spec)
    block = H.load_block(spec)
    shapes = block.weight_shapes(spec)
    H.check_tree(shapes, cfg)
    w = H.make_weights(shapes, cfg.dtype, 1234567, jax.devices()[0])
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(w)[0]
    for path, leaf in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint8).tobytes())
    toks = jnp.asarray(np.random.default_rng(5).integers(0, spec["vocab_size"], 256)
                       .astype(np.int32))
    read = jnp.arange(256, dtype=jnp.int32)
    got = [h.hexdigest()]
    with jax.default_matmul_precision("highest"):
        for fp8 in (False, True):
            logits = R._fn(block, spec, fp8)(w, toks, read)
            got.append(hashlib.sha256(np.asarray(logits).tobytes()).hexdigest())
    assert tuple(got) == rec.DIGESTS[name]


def _trinity_smoke(H):
    """``trinity-mini.ep8`` at a size the CPU holds, in float32: 2 dense
    layers in the first period of 4 and two periods after it, a window
    of 8, top-2 of 8 experts with 4 held, one shared expert."""
    spec = copy.deepcopy(H.load_config("trinity-mini.ep8"))
    spec.update(num_hidden_layers=12, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, intermediate_size=96,
                moe_intermediate_size=32, vocab_size=256, sliding_window=8,
                num_experts=4, num_experts_per_tok=2, torch_dtype="float32",
                layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 3)
    spec["published"]["num_experts"] = 8
    program = {"window": 8, "moe.n_experts": 8, "moe.top_k": 2,
               "moe.d_ff_expert": 32, "moe.n_held": 4}
    spec["program"].update(program)
    spec["departures"].update({k: "smoke size" for k in program})
    return spec


def test_afmoe_reference_equals_the_programs_forward(H):
    from repro.models import forward

    spec = _trinity_smoke(H)
    cfg = H.model_config(spec)
    block = H.load_block(spec)
    shapes = block.weight_shapes(spec)
    H.check_tree(shapes, cfg)
    w = H.make_weights(shapes, cfg.dtype, 2147483659, jax.devices()[0])
    S = 40                                    # several windows long
    toks = jnp.asarray(np.random.default_rng(9).integers(0, 256, S), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = block.forward(w, toks, jnp.arange(S, dtype=jnp.int32), spec=spec,
                            fp8=False)
        got = forward(w, cfg, toks[None])[0][0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-4,
                               rtol=2e-4)


def test_afmoe_work_counts_the_window_and_the_held_share(H):
    spec = H.load_config("trinity-mini.ep8")
    block = H.load_block(spec)
    short, _ = block.attention_work(spec, [1000])
    long_, _ = block.attention_work(spec, [4096])
    # 24 sliding layers stop at 2048 keys, 8 full layers take all 4096
    assert long_ / short == (24 * 2048 + 8 * 4096) / (32 * 1000)
    flops, bytes_ = block.expert_work(spec, 128)
    assert flops == 2.0 * 3 * 2048 * 1024 * (128 * 8 * 16 / 128) * 30
    everything = 2.0 * 3 * 2048 * 1024 * 16 * 30
    assert 0.99 * everything < bytes_ < everything     # 128 tokens touch ~all
    assert block.expert_work(spec, 1)[1] == pytest.approx(everything * 8 / 128)


def test_trinity_tree_check_passes_at_published_widths(H):
    spec = H.load_config("trinity-mini.ep8")
    cfg = H.model_config(spec)
    assert (cfg.moe.n_experts, cfg.moe.n_held, cfg.moe.top_k) == (128, 16, 8)
    H.check_tree(H.load_block(spec).weight_shapes(spec), cfg)
