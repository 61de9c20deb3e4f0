"""Shared pieces of the chip benchmark: files by name, weights, the fleet.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

- ``configs/<config>.json``: the model's numbers (Hugging Face key
  names), what was cut, and the fleet shape (batch, length, pool pages);
  its optional ``"block"`` names the block module (default ``dense``)
  and its optional ``"program"`` sets fields of the program's
  ``ModelConfig`` that no published key maps to;
- ``blocks/<block>.py``: everything that depends on the model's block:
  the weights' layout, the float32 reference's forward pass and the
  work counts (see ``blocks/dense.py``);
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
    "torch_dtype": "dtype",
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


def _load_module(path: Path, prefix: str):
    name = f"{prefix}_{path.stem.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The ``read(run)`` function of a metric, found by its name."""
    for stem in (metric, metric.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.is_file():
            return _load_module(path, "bench_metric").read
    raise BenchError(f"no reader for metric {metric!r} under metrics/")


def load_block(spec: Dict[str, Any], where: Path = HERE / "blocks"):
    """The block module a configuration names (``"block"``, else ``dense``).

    A block module provides ``weight_shapes(spec)``,
    ``forward(w, tokens, read_at, *, spec, fp8)``,
    ``decode_step_flops(spec, contexts)``,
    ``prefill_chunk_flops(spec, past, chunk)`` and
    ``attention_work(spec, contexts, kv_bytes)``: each reads the model's
    numbers from the configuration file ``spec`` alone.
    """
    name = spec.get("block", "dense")
    path = Path(where) / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no block module {name!r}: {path} is missing")
    return _load_module(path, "bench_block")


def _set_field(obj, names, value, key: str):
    """``obj`` with the dotted field ``names`` set to ``value``."""
    head, rest = names[0], names[1:]
    if not dataclasses.is_dataclass(obj) or head not in {
            f.name for f in dataclasses.fields(obj)}:
        raise BenchError(f"\"program\" key {key!r}: {type(obj).__name__} "
                         f"has no field {head!r}")
    if rest:
        value = _set_field(getattr(obj, head), rest, value, key)
    return dataclasses.replace(obj, **{head: value})


def model_config(spec: Dict[str, Any]):
    """The ``ModelConfig`` a configuration file describes.

    The published keys of ``MODEL_KEYS`` first, then the file's
    ``"program"`` fields: a dotted name reaches a nested group
    (``"moe.n_experts"``), and ``null`` clears a field (``"mla"``).
    Each ``"program"`` field needs its reason under ``"departures"``,
    and none may set a precision: that is the published ``torch_dtype``.
    """
    from repro.configs import get_config

    base = get_config(spec["arch"])
    kw = {MODEL_KEYS[k]: spec[k] for k in MODEL_KEYS if k in spec}
    kw["name"] = spec["name"]
    cfg = dataclasses.replace(base, **kw)
    departures = spec.get("departures", {})
    for key, value in spec.get("program", {}).items():
        if key.split(".")[-1].endswith("dtype"):
            raise BenchError(f"\"program\" key {key!r}: a precision is set by "
                             "the published \"torch_dtype\" only")
        if not departures.get(key):
            raise BenchError(f"\"program\" key {key!r} has no reason under "
                             "\"departures\"")
        cfg = _set_field(cfg, key.split("."), value, key)
    return cfg


def derive_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for one use of the run's ``--seed``."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0]) >> 1


# ---------------------------------------------------------------------------
# weights: made here, from the seed, on the device, in one jitted call
# ---------------------------------------------------------------------------
def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, val in flat.items():
        node = tree
        *heads, leaf = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[leaf] = val
    return tree


def check_tree(shapes: Dict[str, Any], cfg) -> None:
    """Raise unless the block's weights have the program's tree.

    ``shapes`` is the block's ``weight_shapes``; the program's tree is
    ``init_params(cfg, key)[0]`` traced with ``jax.eval_shape``, so
    nothing is made or compiled.  Paths, shapes and dtypes must match.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import init_params

    prog = jax.eval_shape(lambda k: init_params(cfg, k)[0], jax.random.key(0))
    have = {
        "/".join(str(getattr(k, "key", k)) for k in path):
            (tuple(x.shape), jnp.dtype(x.dtype).name)
        for path, x in jax.tree_util.tree_flatten_with_path(prog)[0]
    }
    dtype = jnp.dtype(cfg.dtype).name
    want = {path: (tuple(shape), dtype) for path, (shape, _) in shapes.items()}
    bad = sorted(p for p in have.keys() | want.keys() if have.get(p) != want.get(p))
    if bad:
        raise BenchError(
            f"the block's weights differ from the program's for {cfg.name!r} at "
            + "; ".join(f"{p}: block {want.get(p)}, program {have.get(p)}"
                        for p in bad))


def make_weights(shapes: Dict[str, Any], dtype, seed: int, device):
    """Random weights in the served dtype, made on ``device`` in one call.

    ``shapes`` is a block's ``weight_shapes``: ``{path: (shape, std)}``,
    ``std`` of ``None`` for ones (a norm's gain).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    dtype = jnp.dtype(dtype)

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


def build_fleet(spec, traffic, seed: int, devices, block, weights=None):
    """Check the block's weight tree, parse the serve arguments, build
    the replicas, warm every shape.

    Returns ``(serve_module, args, engines, weights)``.  The tree check
    raises :class:`BenchError` before anything is compiled.  Warm-up
    covers every decode bucket and the prefill shapes of the mix's
    prompt, for a prefix-cache miss and for a hit on the whole shared
    prompt.
    """
    from repro.launch import serve
    from repro.serving import build_engines

    cfg = model_config(spec)
    shapes = block.weight_shapes(spec)
    check_tree(shapes, cfg)
    args = serve.build_parser().parse_args(serve_argv(spec, traffic))
    if weights is None:
        weights = make_weights(shapes, cfg.dtype, seed, devices[0])
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
