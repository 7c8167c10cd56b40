"""Pallas kernels + pure-jnp references.

Every device path imports this package first, so the persistent compile
cache is placed here.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left
to JAX; otherwise the cache lives at ``.jax_cache/`` in the root of the
checkout.  The minimum compile time is 0 so the sub-second kernel
compiles are kept too.
"""
import os
from pathlib import Path

import jax

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      str(Path(__file__).resolve().parents[3] / ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

from . import ops, ref, slowdown_kernel, timeline_kernel  # noqa: E402
