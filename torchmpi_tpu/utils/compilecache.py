"""Persistent XLA compilation cache: one rule for where it lives.

JAX's persistent compilation cache turns one successful compile into a
disk artifact that every later process reuses.  The directory is part of
the cache key's lookup, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, and this
  module sets no directory at all (the operator placed the cache);
- unset: ``<checkout>/.jax_compile_cache`` (git-ignored), a fixed path
  so a second run in the same checkout hits what the first one wrote.

The reference had no analog (compilation is not a phase in its MPI/CUDA
world).  Enabling is best-effort by design: a backend that cannot
serialize executables just misses the cache (jax logs and moves on).
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_persistent_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Idempotent.  Call before the first compile.

    Thresholds cache aggressively (min compile time 1 s, no minimum
    entry size): a cold process otherwise recompiles every step program.
    """
    import jax

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not directory:
        directory = DEFAULT_DIR
        os.makedirs(directory, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory
