"""Run environment of the benchmark: the chip, its peaks, the compile cache.

The compile-cache helper is a copy of ``benchmarks/_env.use_compile_cache``
with one difference the benchmark needs: the directory is always inside
the checkout, at a fixed path, whatever the machine's environment says,
so two checkouts never share compiled programs.
"""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
OUT_DIR = os.path.join(ROOT, ".bench_cache", "out")


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def use_compile_cache(path: str = CACHE_DIR) -> int:
    """Turn JAX's persistent compilation cache on at ``path``; every
    program is cached, however short its compile.  Returns how many
    entries the cache held before this process."""
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return len(os.listdir(path))


def devices(n_chips: int):
    """The first ``n_chips`` accelerator devices; raises ``NoChip``."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform == "cpu":
        raise NoChip(f"no accelerator: JAX runs on {jax.default_backend()}")
    if len(devs) < n_chips:
        raise NoChip(f"cell needs {n_chips} chips, JAX sees {len(devs)}")
    return devs[:n_chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (bench/peaks.json)."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
