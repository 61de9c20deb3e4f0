"""Block modules: the dense block's identity, and a block from new files.

- ``blocks/dense.py`` counts the same work as the counters it replaced,
  for both benchmark configurations, and at a smoke size on the CPU
  makes the same weights and reference logits, bit for bit: the sha256
  digests below were recorded from the harness before the blocks were
  split out (weights from ``make_weights`` at seed 1234567, logits of
  the float32 reference and of its fp8 control over 256 tokens drawn
  from ``default_rng(5)``).
- A configuration's ``"program"`` key sets ``ModelConfig`` fields,
  dotted and ``null`` included, and refuses an unknown one, one with no
  reason under ``"departures"`` and one that sets a precision.
- ``tests/testdata/moe_test.py`` and ``moe-smoke.json``, a block with
  routed and shared experts written apart from the program, run through
  ``run.run_cell``: clean runs are correct, the fp8 control is not, and
  a block whose weight tree differs from the program's stops the run
  before any engine is built.

Run by hand::

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

from __future__ import annotations

import copy
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
TESTDATA = Path(__file__).resolve().parent / "testdata"
sys.path.insert(0, str(HERE))

import harness as H  # noqa: E402
import reference as R  # noqa: E402
from test_harness_cpu import CELL, alter_tokens, run_smoke  # noqa: E402

# config -> (decode_step_flops(32 rows at 100), attention_work([512, 1024]),
#            prefill_chunk_flops(past 448, chunk 64))
WORK = {
    "stablelm-1.6b": (92_702_507_008, (301_989_888, 301_989_888), 164_297_179_136),
    "internlm2-20b.8l": (236_734_906_368, (301_989_888, 50_331_648), 406_615_228_416),
}

SMOKE = {
    "stablelm-1.6b": dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                          num_key_value_heads=4, head_dim=16, intermediate_size=128,
                          vocab_size=256),
    "internlm2-20b.8l": dict(num_hidden_layers=2, hidden_size=96, num_attention_heads=6,
                             num_key_value_heads=2, head_dim=16, intermediate_size=160,
                             vocab_size=320),
}
# config -> (weights, reference logits, fp8 control logits)
DIGESTS = {
    "stablelm-1.6b": (
        "d89117db1694a55ae70b2cb4d59dc42b4deb69bee1731288f23ee0fa78e1a044",
        "aeff02073adaadfcc90018ecd574bee63d5857b97181fa7ed151dc076a721eea",
        "deb4debc08125480f9260175a8c40c8f7c2bc24861c2de80920f7609d070f411"),
    "internlm2-20b.8l": (
        "9663335f848f8ea9caf332481f175d05e95710424fed24053b7000c3db7211ff",
        "c6f843d0617b8a3a32a460baca59db138fca9e8e8ec75526c6da6291b7ce08ec",
        "4cf212d0363babea8a45e65d07b0eab7a4286160f0abe7a063acde2a0da4ad5d"),
}


def moe_spec():
    return json.loads((TESTDATA / "moe-smoke.json").read_text())


@pytest.mark.parametrize("name", sorted(WORK))
def test_dense_block_counts_the_same_work(name):
    spec = H.load_config(name)
    block = H.load_block(spec)
    decode, attn, prefill = WORK[name]
    assert block.decode_step_flops(spec, [100] * 32) == decode
    assert block.attention_work(spec, [512, 1024]) == attn
    assert block.prefill_chunk_flops(spec, 448, 64) == prefill


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_dense_block_makes_the_same_weights_and_logits(name):
    import jax
    import jax.numpy as jnp

    spec = copy.deepcopy(H.load_config(name))
    spec.update(SMOKE[name])
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
    assert tuple(got) == DIGESTS[name]


def test_program_key_sets_config_fields():
    spec = moe_spec()
    cfg = H.model_config(spec)
    assert cfg.mla is None
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.first_dense,
            cfg.moe.impl) == (8, 2, 0, "dense")
    assert cfg.dtype == "float32"              # the published torch_dtype
    assert cfg.n_kv_heads == 2                 # the published keys still apply
    for bad, why in (({"moe.no_such_field": 1}, "no field"),
                     ({"no_such_field": 1}, "no field"),
                     ({"mla.kv_lora_rank": 4}, "no field"),  # mla is cleared
                     ({"dtype": "bfloat16"}, "torch_dtype"),
                     ({"moe.combine_dtype": "bfloat16"}, "torch_dtype")):
        spec = moe_spec()
        spec["program"].update(bad)
        spec["departures"].update({k: "a reason" for k in bad})
        with pytest.raises(H.BenchError, match=why):
            H.model_config(spec)
    spec = moe_spec()
    del spec["departures"]["moe.impl"]
    with pytest.raises(H.BenchError, match="'moe.impl' has no reason"):
        H.model_config(spec)


@pytest.fixture(scope="module")
def moe_clean():
    spec = moe_spec()
    return run_smoke(spec=spec, block=H.load_block(spec, TESTDATA))


def test_moe_block_clean_run_is_correct(moe_clean):
    assert moe_clean["correct"], moe_clean["checks"]
    assert moe_clean["checks"]["sampled_tokens"]["value"] > 50


@pytest.mark.parametrize("fault", ["fp8_control", "altered_token"])
def test_moe_block_faults_are_not_correct(moe_clean, fault):
    spec = moe_spec()
    limit = H.load_limits(CELL)["max_logit_gap"]["limit"]
    out = run_smoke(spec=spec, block=H.load_block(spec, TESTDATA), limit=limit,
                    control=fault == "fp8_control",
                    fault=alter_tokens if fault == "altered_token" else None)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > limit


def test_block_tree_unlike_the_programs_stops_before_serving(monkeypatch):
    import repro.serving

    def no_engines(*a, **k):
        raise AssertionError("engines were built")

    monkeypatch.setattr(repro.serving, "build_engines", no_engines)
    spec = moe_spec()
    spec["program"]["moe.d_ff_expert"] = 48     # the block still reads 32
    with pytest.raises(H.BenchError, match="moe/w_gate"):
        run_smoke(spec=spec, block=H.load_block(spec, TESTDATA))
    with pytest.raises(H.BenchError, match="moe/router"):
        run_smoke(spec=moe_spec(), block=H.load_block({}))   # dense for an MoE
