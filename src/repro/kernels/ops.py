"""jit'd public wrappers for the Pallas kernels with jnp fallbacks.

Dispatch policy: on TPU backends the Pallas path compiles natively; on
CPU (this container) the default is the pure-jnp reference path, with
``interpret=True`` available everywhere for kernel-correctness tests.
Models call these wrappers (cfg.use_pallas) so swapping the backend is a
config flip, not a code change.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention
from .flash_attention import flash_attention


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "backend"))
def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              softcap: float = 0.0, scale: float | None = None,
              backend: str = "auto"):
    """Fused attention: backend in {auto, pallas, interpret, ref}."""
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "ref":
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, scale=scale)
    return flash_attention(q, k, v, causal=causal, window=window,
                           softcap=softcap, scale=scale,
                           interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "backend"))
def decode_attn(q, k, v, valid, *, softcap: float = 0.0,
                scale: float | None = None, backend: str = "auto"):
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "ref"
    if backend == "ref":
        return ref.decode_attention_ref(q, k, v, valid, softcap=softcap,
                                        scale=scale)
    return decode_attention(q, k, v, valid, softcap=softcap, scale=scale,
                            interpret=(backend == "interpret"))

