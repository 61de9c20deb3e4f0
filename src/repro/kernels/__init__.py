"""Pallas TPU kernels for the LLM-executor hot spots.

Each kernel ships three files per the repo contract:
- ``<name>.py`` — pl.pallas_call + explicit BlockSpec VMEM tiling;
- ``ops.py``    — jit'd dispatch (pallas on TPU, oracle elsewhere);
- ``ref.py``    — pure-jnp oracle, the semantics ground truth.

Kernels: flash_attention (prefill/train), decode_attention (serving decode
hot spot, slot caches), paged_attention (serving decode over paged KV
pools with block tables, with an optional sliding window), expert_ffn
(grouped SwiGLU over the experts a device holds), rmsnorm (fused norm),
ssm_scan (Mamba selective scan).
"""

from . import ops, ref

__all__ = ["ops", "ref"]
