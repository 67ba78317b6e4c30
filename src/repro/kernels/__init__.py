"""repro.kernels — Pallas TPU kernels + jnp oracles.

  flash_attention  — blockwise causal/SWA/softcap attention (train/prefill)
  decode_attention — single-token GQA decode over long KV caches
  ops              — jit'd dispatchers (pallas | interpret | ref)
  ref              — pure-jnp ground truth for the kernels above, and
                     the reaction-point updates the CC stages are
                     checked against
"""

from . import ops, ref
from .flash_attention import flash_attention
from .decode_attention import decode_attention

__all__ = ["ops", "ref", "flash_attention", "decode_attention"]
