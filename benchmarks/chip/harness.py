"""Shared pieces of the chip benchmark: files by name, weights, the fleet.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

- ``configs/<config>.json``: the model's numbers (Hugging Face key
  names), what was cut, and the fleet shape (batch, length, pool pages);
- ``traffic/<traffic>.json``: the traffic mix's parameters;
- ``limits/<cell>.json``: the limits ``correct`` is decided by;
- ``metrics/<metric>.py`` (or ``metrics/<stem>.py`` for a metric named
  ``<stem>.<split>``): one reader per metric.

The program under test is imported from ``<checkout>/src``; the
benchmark reads nothing else of the repository.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SRC = ROOT / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

# config-file key -> ModelConfig field
MODEL_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}


class BenchError(Exception):
    """The benchmark's files or the machine do not allow a run."""


def _json(path: Path) -> Dict[str, Any]:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload named {name!r} in BENCHMARK.json")


def load_config(name: str) -> Dict[str, Any]:
    return _json(HERE / "configs" / f"{name}.json")


def load_traffic(name: str) -> Dict[str, Any]:
    return _json(HERE / "traffic" / f"{name}.json")


def load_limits(cell: str) -> Dict[str, Any]:
    return _json(HERE / "limits" / f"{cell}.json")


def cell_metrics(bench: Dict[str, Any], cell: str, trace: bool):
    """The metric entries this cell reports in a run of this kind."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(metric: str):
    """The ``read(run)`` function of a metric, found by its name."""
    for stem in (metric, metric.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_').replace('-', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise BenchError(f"no reader for metric {metric!r} under metrics/")


def model_config(spec: Dict[str, Any]):
    """The ``ModelConfig`` a configuration file describes."""
    from repro.configs import get_config

    base = get_config(spec["arch"])
    kw = {MODEL_KEYS[k]: spec[k] for k in MODEL_KEYS if k in spec}
    kw["name"] = spec["name"]
    return dataclasses.replace(base, **kw)


def derive_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for one use of the run's ``--seed``."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0]) >> 1


# ---------------------------------------------------------------------------
# weights: made here, from the seed, on the device, in one jitted call
# ---------------------------------------------------------------------------
def weight_shapes(cfg) -> Dict[str, Any]:
    """Layout and initial scale of every weight of a dense decoder.

    Returns ``{path: (shape, std)}``; ``std`` of ``None`` means ones (a
    norm's gain).  Paths follow the parameter tree the program's paged
    model reads: ``blocks/0/...`` leaves are stacked over layers.
    """
    L, d, ff, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab
    q, kv = cfg.n_heads * cfg.hd, cfg.n_kv_heads * cfg.hd
    return {
        "embedding": ((V, d), 1 / math.sqrt(d)),
        "lm_head": ((d, V), 1 / math.sqrt(d)),
        "final_norm": ((d,), None),
        "blocks/0/norm1": ((L, d), None),
        "blocks/0/norm2": ((L, d), None),
        "blocks/0/attn/wq": ((L, d, q), 1 / math.sqrt(d)),
        "blocks/0/attn/wk": ((L, d, kv), 1 / math.sqrt(d)),
        "blocks/0/attn/wv": ((L, d, kv), 1 / math.sqrt(d)),
        "blocks/0/attn/wo": ((L, q, d), 1 / math.sqrt(q)),
        "blocks/0/mlp/w_gate": ((L, d, ff), 1 / math.sqrt(d)),
        "blocks/0/mlp/w_up": ((L, d, ff), 1 / math.sqrt(d)),
        "blocks/0/mlp/w_down": ((L, ff, d), 1 / math.sqrt(ff)),
    }


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, val in flat.items():
        node = tree
        *heads, leaf = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[leaf] = val
    return tree


def make_weights(cfg, seed: int, device):
    """Random weights in the served dtype, made on ``device`` in one call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    shapes = weight_shapes(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def make(key):
        out = {}
        for i, (path, (shape, std)) in enumerate(sorted(shapes.items())):
            if std is None:
                out[path] = jnp.ones(shape, dtype)
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                out[path] = (x * std).astype(dtype)
        return _nest(out)

    fn = jax.jit(make, out_shardings=SingleDeviceSharding(device))
    return fn(jax.random.key(derive_seed(seed, 1)))


# ---------------------------------------------------------------------------
# the served fleet, through the program's own command line and builders
# ---------------------------------------------------------------------------
def serve_argv(spec: Dict[str, Any], traffic: Dict[str, Any]):
    """``repro.launch.serve`` arguments for this configuration and mix."""
    fleet = spec["fleet"]
    argv = [
        "--arch", spec["arch"], "--engine", "paged",
        "--mix", traffic["profile_mix"], "--scheduler", "llmsched",
        "--replicas", str(fleet["replicas"]),
        "--max-batch", str(fleet["max_batch"]),
        "--max-len", str(fleet["max_len"]),
        "--page-size", str(fleet["page_size"]),
        "--kv-pages", ",".join([str(fleet["pool_pages"])] * fleet["replicas"]),
        "--shared-prompt", str(traffic["shared_prompt_tokens"]),
        "--regular", str(traffic["tool_slots"]),
        "--token-scale", "1",
        # LLMSched's exploration stream is the deployment's, not the
        # run's: every seed meets the same scheduler
        "--seed", str(traffic["pool_seed"]),
    ]
    if fleet["prefix_cache"]:
        argv.append("--prefix-cache")
    return argv


def build_fleet(spec, traffic, seed: int, devices, weights=None):
    """Parse the serve arguments, build the replicas, warm every shape.

    Returns ``(serve_module, args, engines, weights)``.  Warm-up covers
    every decode bucket and the prefill shapes of the mix's prompt, for
    a prefix-cache miss and for a hit on the whole shared prompt.
    """
    from repro.launch import serve
    from repro.serving import build_engines

    cfg = model_config(spec)
    args = serve.build_parser().parse_args(serve_argv(spec, traffic))
    if weights is None:
        weights = make_weights(cfg, seed, devices[0])
    engines = build_engines(cfg, serve.config_from_args(args), params=weights)
    shared = traffic["shared_prompt_tokens"]
    plen = shared + 2           # the cluster's prompt: shared + 2-token suffix
    ps = spec["fleet"]["page_size"]
    cached = sorted({0, (shared // ps) * ps} if spec["fleet"]["prefix_cache"] else {0})
    for eng in engines:
        eng.warm_up([plen], cached)
    return serve, args, engines, weights


def pct(values, q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), q))
