"""Ahead-of-time compiles for one TPU v5e chip, at served widths.

The TPU compiler is installed with JAX, and it compiles for a chip
that is described rather than attached.  These tests compile the
served path's Pallas kernels (``interpret=False``) and whole
full-width decode and prefill steps, then check that each program
holds a Mosaic kernel (``tpu_custom_call``) and fits one chip's 16 GiB,
and that a step program holds the KV pools in place: its optimised HLO
makes no copy, relayout, slice or update of a whole pool, and its
temporaries are smaller than one layer's pool.  Nothing runs: what a
compile proves is that the chip's compiler accepts the program, which
interpret mode cannot show.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  The persistent compilation cache is off
around these compiles, since a program compiled for an absent chip
cannot be read back.
"""

import dataclasses
import json
import math
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.expert_ffn import ROW_TILE, expert_ffn
from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_prefill_attention,
)
from repro.kernels.rmsnorm import rmsnorm
from repro.models import init_params
from repro.models.paged import (
    init_paged_pools,
    paged_decode_step,
    paged_prefill_chunk,
)

HBM_BYTES = 16 * 2**30          # one v5e chip
H = K = 32                      # stablelm-1.6b heads (MHA)
HD, PAGE = 64, 16
BATCH, PAGES_PER_SEQ = 8, 32    # 8 sequences of 512 tokens
POOL_PAGES = 1 + BATCH * PAGES_PER_SEQ
LAYERS = 2                      # stacked pools the kernels index by layer


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
    pool = _spec((LAYERS, POOL_PAGES, PAGE, K * HD), pool_dt, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    scales = {}
    if kv == "int8":
        scale_pool = _spec((LAYERS, POOL_PAGES, 1, PAGE * K), jnp.float32,
                           one_chip)
        scales = {"k_scales": scale_pool, "v_scales": scale_pool}
    if kernel == "decode":
        lowered = paged_decode_attention.lower(
            _spec((BATCH, H, HD), jnp.bfloat16, one_chip), pool, pool,
            _spec((BATCH, PAGES_PER_SEQ), jnp.int32, one_chip),
            _spec((BATCH,), jnp.int32, one_chip), layer,
            interpret=False, **scales,
        )
    else:
        lowered = paged_prefill_attention.lower(
            _spec((64, H, HD), jnp.bfloat16, one_chip), pool, pool,
            _spec((PAGES_PER_SEQ,), jnp.int32, one_chip), 64, layer,
            interpret=False, **scales,
        )
    _check(lowered.compile())


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_windowed_paged_kernel_compiles_for_v5e(one_chip, kernel, kv):
    """trinity-mini's heads (GQA 32 over 4 x 128) with its 2048-token
    window, past the window in decode and a chunk whose grid starts
    inside the context in prefill."""
    h, k, hd, npp = 32, 4, 128, 256
    pool_dt = jnp.bfloat16 if kv == "bf16" else jnp.int8
    pool = _spec((LAYERS, POOL_PAGES, PAGE, k * hd), pool_dt, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    scales = {}
    if kv == "int8":
        scale_pool = _spec((LAYERS, POOL_PAGES, 1, PAGE * k), jnp.float32,
                           one_chip)
        scales = {"k_scales": scale_pool, "v_scales": scale_pool}
    if kernel == "decode":
        lowered = paged_decode_attention.lower(
            _spec((BATCH, h, hd), jnp.bfloat16, one_chip), pool, pool,
            _spec((BATCH, npp), jnp.int32, one_chip),
            _spec((BATCH,), jnp.int32, one_chip), layer,
            interpret=False, window=2048, **scales,
        )
    else:
        lowered = paged_prefill_attention.lower(
            _spec((64, h, hd), jnp.bfloat16, one_chip), pool, pool,
            _spec((npp,), jnp.int32, one_chip), 3008, layer,
            interpret=False, window=2048, **scales,
        )
    _check(lowered.compile())


@pytest.mark.parametrize("rows", [1, 64, 128])
def test_expert_ffn_compiles_for_v5e(one_chip, rows):
    """trinity-mini's 16 held experts (2048 -> 1024 -> 2048) for a decode
    batch of ``rows`` tokens at top-8."""
    E, d, f, k = 16, 2048, 1024, 8
    max_tiles = -(-rows // ROW_TILE)
    tiles = min(E * max_tiles, -(-(rows * k + E * (ROW_TILE - 1)) // ROW_TILE))
    w = lambda *s: _spec(s, jnp.bfloat16, one_chip)  # noqa: E731
    L = 7                       # the expert weights stacked over a scan
    lowered = expert_ffn.lower(
        w(tiles * ROW_TILE, d), w(L, E, d, f), w(L, E, d, f), w(L, E, f, d),
        _spec((E,), jnp.int32, one_chip), _spec((E,), jnp.int32, one_chip),
        max_tiles, _spec((), jnp.int32, one_chip), interpret=False,
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


# one HLO instruction: name, element type, dims, opcode
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
_IN_PLACE = {"parameter", "get-tuple-element", "tuple", "bitcast", "scatter"}


def _whole_pool_ops(hlo: str, pools) -> list:
    """Instructions whose result is a whole pool leaf, one layer's or the stack's.

    In-place writes are allowed: a scatter (or a fusion whose root is a
    scatter) and a kernel whose output aliases its operand.
    """
    sizes = set()
    for leaf in jax.tree.leaves(pools):
        if leaf.ndim == 4:              # a pool (not the routed-token count)
            sizes |= {math.prod(leaf.shape), math.prod(leaf.shape[1:])}
    scatter_rooted = set()
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
        elif line.lstrip().startswith("ROOT ") and " scatter(" in line:
            scatter_rooted.add(comp)
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(3).split(",") if d]
        op = m.group(4)
        if math.prod(dims) not in sizes or op in _IN_PLACE:
            continue
        called = re.search(r"calls=%([\w.-]+)", line)
        if op == "fusion" and called and called.group(1) in scatter_rooted:
            continue
        if op == "custom-call" and "output_to_operand_aliasing" in line:
            continue
        found.append(f"{op} {m.group(2)}{dims} ({m.group(1)})")
    return found


# the served pools: stablelm-1.6b's 24 layers over 1,532 pages, and
# internlm2-20b's widths (GQA 48 over 8 x 128) in 8 layers over 4,912
# pages.  How the compiler places a pool depends on its size (one small
# enough for the chip's on-core memory may be staged there whole), so
# the sizes are the served ones.
TRINITY = json.loads((Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
                      / "configs" / "trinity-mini.ep8.json").read_text())


def _trinity_served():
    """trinity-mini as the chip benchmark serves it: 16 of 128 experts held."""
    cfg = get_config("trinity-mini")
    held = TRINITY["program"]["moe.n_held"]
    return cfg.with_(moe=dataclasses.replace(cfg.moe, n_held=held))


SERVED = {
    "stablelm-1.6b": (lambda: get_config("stablelm-1.6b"), 1532),
    "gqa-8x128": (
        lambda: dataclasses.replace(get_config("internlm2-20b"), n_layers=8),
        4912,
    ),
    # leading layers with a pool of their own, windowed and NoPE layers,
    # held experts and the routed-token count carried with the pools
    "trinity-mini.ep8": (_trinity_served, TRINITY["fleet"]["pool_pages"]),
}


def _step(cfg, program):
    if program == "decode":
        return jax.jit(
            lambda p, pools, toks, bt, lens: paged_decode_step(
                p, cfg, pools, toks, bt, lens
            ),
            donate_argnums=(1,),
        )
    return jax.jit(
        lambda p, pools, toks, bt: paged_prefill_chunk(p, cfg, pools, toks, bt, 64),
        donate_argnums=(1,),
    )


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_trinity_steps_fit_one_chip(one_chip, monkeypatch, program):
    """The served trinity-mini programs, decode at the cell's 128 rows,
    compile with every kernel and fit 16 GiB at the configured pool."""
    monkeypatch.setattr(ops, "_default_impl", lambda: "pallas")
    cfg = _trinity_served()
    fleet = TRINITY["fleet"]
    on_chip = lambda s: _spec(s.shape, s.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))[0]))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_paged_pools(cfg, fleet["pool_pages"], fleet["page_size"])))
    i32 = lambda *s: _spec(s, jnp.int32, one_chip)  # noqa: E731
    B, npp = fleet["max_batch"], fleet["max_len"] // fleet["page_size"]
    args = ((i32(B), i32(B, npp), i32(B)) if program == "decode"
            else (i32(1, 64), i32(npp)))
    compiled = _step(cfg, program).lower(params, pools, *args).compile()
    _check(compiled)
    hlo = compiled.as_text()
    kernels = ["paged_decode" if program == "decode" else "paged_prefill",
               "expert_ffn"]
    for name in kernels:
        assert name in hlo
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < 0.9 * HBM_BYTES, used


# trinity's int8 scale pools are small enough (9 MB a position) for the
# compiler to stage whole on-core: its cell serves bf16 pages
IN_PLACE = [(model, program, kv) for model in sorted(SERVED)
            for program in ("decode", "prefill") for kv in ("fp32", "int8")
            if not (model == "trinity-mini.ep8" and kv == "int8")]


@pytest.mark.parametrize("model,program,kv", IN_PLACE)
def test_step_holds_pools_in_place(one_chip, monkeypatch, model, program, kv):
    """A step program writes its tokens' slots and copies no whole pool."""
    monkeypatch.setattr(ops, "_default_impl", lambda: "pallas")
    make_cfg, pages = SERVED[model]
    cfg = make_cfg()
    on_chip = lambda s: _spec(s.shape, s.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))[0]
    ))
    pools = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_paged_pools(cfg, pages, PAGE, kv)
    ))
    i32 = lambda *s: _spec(s, jnp.int32, one_chip)  # noqa: E731
    if program == "decode":
        args = (i32(BATCH), i32(BATCH, PAGES_PER_SEQ), i32(BATCH))
    else:
        args = (i32(1, 64), i32(PAGES_PER_SEQ))
    compiled = _step(cfg, program).lower(params, pools, *args).compile()
    _check(compiled)
    hlo = compiled.as_text()
    assert _whole_pool_ops(hlo, pools) == []
    if kv == "fp32":
        # nor is a layer's weight matrix relaid (a per-head RoPE made the
        # compiler copy each layer's q/k projection weights; the int8
        # quantizer's per-head amax still does so for k)
        # (the unscanned leading layers' own too; routers and norms are
        # left out, as small as a prefill chunk's activations)
        projections = ("wq", "wk", "wv", "wo", "wg", "w_gate", "w_up", "w_down")
        layer_weights = set()
        for path, w in jax.tree_util.tree_flatten_with_path(params)[0]:
            top, name = path[0].key, path[-1].key
            if name not in projections:
                continue
            if top == "blocks" and w.ndim == 3:
                layer_weights.add(math.prod(w.shape[1:]))
            elif top.startswith("prefix_") and w.ndim == 2:
                layer_weights.add(math.prod(w.shape))
        relaid = [m.group(0) for m in map(_INSTR.match, hlo.splitlines())
                  if m and m.group(4) == "copy"
                  and math.prod(int(d) for d in m.group(3).split(",") if d)
                  in layer_weights]
        assert relaid == []
    k = pools["blocks"]["0"]["k"]
    layer_bytes = math.prod(k.shape[1:]) * k.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, (mem.temp_size_in_bytes,
                                                  layer_bytes)
    # the donated pools are the outputs
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert mem.alias_size_in_bytes >= pool_bytes
