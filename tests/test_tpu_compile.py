"""Ahead-of-time compiles for one TPU v5e chip, at stablelm-1.6b widths.

The TPU compiler is installed with JAX, and it compiles for a chip
that is described rather than attached.  These tests compile the
served path's Pallas kernels (``interpret=False``) and one whole
full-width decode step, then check that each program holds a Mosaic
kernel (``tpu_custom_call``) and fits one chip's 16 GiB.  Nothing
runs: what a compile proves is that the chip's compiler accepts the
program, which interpret mode cannot show.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  The persistent compilation cache is off
around these compiles, since a program compiled for an absent chip
cannot be read back.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_prefill_attention,
)
from repro.kernels.rmsnorm import rmsnorm
from repro.models import init_params
from repro.models.paged import init_paged_pools, paged_decode_step

HBM_BYTES = 16 * 2**30          # one v5e chip
H = K = 32                      # stablelm-1.6b heads (MHA)
HD, PAGE = 64, 16
BATCH, PAGES_PER_SEQ = 8, 32    # 8 sequences of 512 tokens
POOL_PAGES = 1 + BATCH * PAGES_PER_SEQ


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host, with the persistent cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, kv):
    pool_dt = jnp.bfloat16 if kv == "bf16" else jnp.int8
    pool = _spec((POOL_PAGES, PAGE, K, HD), pool_dt, one_chip)
    scales = {}
    if kv == "int8":
        scales = {
            "k_scales": _spec((POOL_PAGES, PAGE, K), jnp.float32, one_chip),
            "v_scales": _spec((POOL_PAGES, PAGE, K), jnp.float32, one_chip),
        }
    if kernel == "decode":
        lowered = paged_decode_attention.lower(
            _spec((BATCH, H, HD), jnp.bfloat16, one_chip), pool, pool,
            _spec((BATCH, PAGES_PER_SEQ), jnp.int32, one_chip),
            _spec((BATCH,), jnp.int32, one_chip),
            interpret=False, **scales,
        )
    else:
        lowered = paged_prefill_attention.lower(
            _spec((64, H, HD), jnp.bfloat16, one_chip), pool, pool,
            _spec((PAGES_PER_SEQ,), jnp.int32, one_chip), 64,
            interpret=False, **scales,
        )
    _check(lowered.compile())


def test_rmsnorm_compiles_for_v5e(one_chip):
    d = get_config("stablelm-1.6b").d_model
    lowered = rmsnorm.lower(
        _spec((BATCH * 64, d), jnp.bfloat16, one_chip),
        _spec((d,), jnp.bfloat16, one_chip),
        interpret=False,
    )
    _check(lowered.compile())


def test_full_width_paged_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    # ops dispatches on the host's backend, which here is the CPU
    monkeypatch.setattr(ops, "_default_impl", lambda: "pallas")
    cfg = get_config("stablelm-1.6b")
    on_chip = lambda s: _spec(s.shape, s.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))[0]
    ))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_paged_pools(cfg, POOL_PAGES, PAGE)
    ))
    step = jax.jit(
        lambda p, pools, toks, bt, lens: paged_decode_step(
            p, cfg, pools, toks, bt, lens
        ),
        donate_argnums=(1,),
    )
    compiled = step.lower(
        params, pools,
        _spec((BATCH,), jnp.int32, one_chip),
        _spec((BATCH, PAGES_PER_SEQ), jnp.int32, one_chip),
        _spec((BATCH,), jnp.int32, one_chip),
    ).compile()
    _check(compiled)
