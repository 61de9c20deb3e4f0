"""Grouped SwiGLU over the experts a device holds, in Pallas.

The rows arrive grouped by expert: expert ``e``'s rows fill row tiles
``tile_start[e] .. tile_start[e] + tile_count[e] - 1`` (each expert's
group starts on a tile boundary; the rest of its last tile is padding).
The grid is ``(held experts, row tiles of the largest group)``.  A step
runs one row tile through one expert's gate, up and down projections:
the expert's three weight blocks are indexed by the expert alone, so
they are fetched once per call and stay while its tiles run, and the
next expert's are fetched behind the current one's work.  Steps past an
expert's last tile repeat its last tile's block indices (nothing is
copied again) and compute nothing.  Group offsets and counts ride in
scalar prefetch, with the layer: the weights are the model's stacked
``(L, E_h, ...)`` arrays, read in place by (layer, expert), so no
layer's experts are sliced out and copied first.

The kernel is named ``expert_ffn`` so that a device trace finds it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Rows per tile: the bf16 sublane tile, so a group's padding stays small.
ROW_TILE = 16


def _kernel(start_ref, count_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    e = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t < count_ref[e])
    def _():
        x = x_ref[...]
        h = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        a = (jax.nn.silu(h) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(
            a, wd_ref[...], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


def _vmem_limit(d: int, f: int, itemsize: int) -> int:
    """Double-buffered weight blocks of one expert plus row tiles, with room."""
    weights = 3 * d * f * itemsize
    rows = 2 * ROW_TILE * d * itemsize
    return int(2 * (weights + rows) + (8 << 20))


@functools.partial(jax.jit, static_argnames=("max_tiles", "interpret"))
def expert_ffn(
    x: jax.Array,            # (M, d) rows grouped by expert, tile-aligned
    w_gate: jax.Array,       # (L, E_h, d, f) stacked over layers
    w_up: jax.Array,         # (L, E_h, d, f)
    w_down: jax.Array,       # (L, E_h, f, d)
    tile_start: jax.Array,   # (E_h,) int32 first row tile of each expert
    tile_count: jax.Array,   # (E_h,) int32 row tiles of each expert
    max_tiles: int,          # static bound on any tile_count
    layer=0,                 # int32 scalar: which of the L layers
    interpret: bool = False,
) -> jax.Array:
    """``y[r] = SwiGLU_e(x[r])`` for the rows of each expert ``e``'s tiles.

    ``M`` is a multiple of :data:`ROW_TILE`.  Rows in no expert's tiles
    are left unwritten: callers read only their own rows.
    """
    M, d = x.shape
    _, E_h, _, f = w_gate.shape
    n_tiles = M // ROW_TILE

    import jax.experimental.pallas.tpu as pltpu

    def rows(e, t, start, count, lyr):
        last = jnp.maximum(count[e] - 1, 0)
        return (jnp.minimum(start[e] + jnp.minimum(t, last), n_tiles - 1), 0)

    def expert(e, t, start, count, lyr):
        return (lyr[0], e, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # (tile_start, tile_count, layer)
        grid=(E_h, max_tiles),
        in_specs=[
            pl.BlockSpec((ROW_TILE, d), rows),
            pl.BlockSpec((None, None, d, f), expert),
            pl.BlockSpec((None, None, d, f), expert),
            pl.BlockSpec((None, None, f, d), expert),
        ],
        out_specs=pl.BlockSpec((ROW_TILE, d), rows),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(d, f, w_gate.dtype.itemsize),
        ),
        interpret=interpret,
        name="expert_ffn",
    )(tile_start.astype(jnp.int32), tile_count.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), x, w_gate, w_up, w_down)
