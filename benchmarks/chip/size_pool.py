#!/usr/bin/env python
"""Size a configuration's KV page pool from a v5e ahead-of-time compile.

Compiles the decode step at the largest batch bucket and the largest
prefill chunk for a described (not attached) TPU v5e chip, at two pool
sizes, reads ``memory_analysis()`` of each, and solves for the largest
pool whose busiest program (arguments + outputs - aliased + temporaries)
stays under ``--fraction`` of the chip's 16 GiB, as vLLM's
``gpu_memory_utilization`` does.  Prints one JSON object with the page
count and the bytes, for the configuration file.  Runs on the CPU: no
chip is used.

Usage::

    JAX_PLATFORMS=cpu python benchmarks/chip/size_pool.py --config stablelm-1.6b
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM_BYTES = 16 * 2**30


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def measure(cfg, fleet, pages: int):
    """Bytes of the decode (largest bucket) and prefill programs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops
    from repro.models import init_params
    from repro.models.paged import (
        init_paged_pools, paged_decode_step, paged_prefill_chunk,
    )

    ops._default_impl = lambda: "pallas"   # the host backend is the CPU
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)  # noqa: E731
    params = jax.tree.map(spec, jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))[0]))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: init_paged_pools(cfg, pages, fleet["page_size"])))
    npp = -(-fleet["max_len"] // fleet["page_size"])
    B = fleet["max_batch"]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    dec = jax.jit(lambda p, pl, t, bt, ln: paged_decode_step(p, cfg, pl, t, bt, ln),
                  donate_argnums=(1,)).lower(
        params, pools, i32(B), i32(B, npp), i32(B)).compile()
    C = 64
    pre = jax.jit(lambda p, pl, t, bt: paged_prefill_chunk(
        p, cfg, pl, t, bt, fleet["max_len"] - 2 * C), donate_argnums=(1,)).lower(
        params, pools, i32(1, C), i32(npp)).compile()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    return {"decode": _program_bytes(dec), "prefill": _program_bytes(pre),
            "weights": weights}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--fraction", type=float, default=0.9)
    opts = ap.parse_args()
    from harness import load_config, model_config
    spec = load_config(opts.config)
    cfg, fleet = model_config(spec), spec["fleet"]
    lo, hi = 257, 1025
    a, b = measure(cfg, fleet, lo), measure(cfg, fleet, hi)
    budget = opts.fraction * HBM_BYTES
    best = None
    for prog in ("decode", "prefill"):
        slope = (b[prog] - a[prog]) / (hi - lo)
        n = int((budget - a[prog]) / slope) + lo
        best = n if best is None else min(best, n)
    got = measure(cfg, fleet, best)
    print(json.dumps({"config": opts.config, "pool_pages": best,
                      "fraction": opts.fraction,
                      "decode_program_bytes": got["decode"],
                      "prefill_program_bytes": got["prefill"],
                      "weight_bytes": got["weights"],
                      "per_page_bytes_slope_decode":
                          (b["decode"] - a["decode"]) / (hi - lo)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
