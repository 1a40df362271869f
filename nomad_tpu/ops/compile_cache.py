"""Where JAX's persistent compilation cache lives.

The wave kernels cost seconds to tens of seconds each to compile and a
fresh machine starts with none of them, so every process of this package
shares one on-disk cache at a fixed place — a directory that moves
between runs never hits: `JAX_COMPILATION_CACHE_DIR` when the operator
set it (JAX reads that variable itself; nothing is set in code), else
`.jax_cache` at the root of the checkout.  JAX's own thresholds stay:
entries are written for compiles of >= 1 s
(`jax_persistent_cache_min_compile_time_secs`) of any size, which admits
every wave kernel and skips the sub-second glue ops.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def place_compile_cache() -> None:
    """Point JAX at the persistent compile cache (read the result from
    `jax.config.jax_compilation_cache_dir`).  Called when `nomad_tpu.ops`
    is first imported, before anything compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
