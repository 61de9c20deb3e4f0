"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing one
CPU device; only dryrun.py sets XLA_FLAGS for 512 host devices.

Every mesh here has ``Auto`` axes: the model code states logical
shardings and leaves propagation to the compiler.  ``jax.make_mesh``
defaults to ``Explicit`` axes, under which a gather or a contraction
over a sharded dimension must name its output sharding.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips with a leading
    ``pod`` axis (data parallel across the inter-pod DCN/ICI links)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes (see the module docstring)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> Optional[Mesh]:
    """Single-host debug mesh over however many devices exist (≥2)."""
    n = len(jax.devices())
    if n < 2:
        return None
    return make_mesh((1, n), ("data", "model"))
