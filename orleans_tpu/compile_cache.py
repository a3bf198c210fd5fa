"""Where JAX keeps compiled programs — the one place the program decides.

A cold start on the chip compiles every kernel shape the run touches, and
a machine that runs one command and is thrown away starts cold every time
unless the persistent compilation cache sits where the next run finds it.
The cache's path is part of nothing but its own lookup, so it must not
move: never a temporary name, a pid or a time.

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself; nothing is set
  in code (the caller placed the cache from outside).
* unset, on an accelerator: ``<checkout>/.jax_cache`` — derived from this
  package's own location, ignored by git — and every compile is kept (the
  engine's per-bucket kernels each compile in well under jax's default
  one-second threshold, and there are many of them).
* unset, on the CPU backend: nothing. No chip call waits for a CPU
  compile, and XLA:CPU's loader logs a machine-feature mismatch error for
  every cached program it reads back — noise the test runs must not carry.

Every entry point that compiles for the device calls
:func:`ensure_compile_cache` before its first compile: ``VectorRuntime``,
``chip_smoke.py``, ``bench.py``, ``benchmarks/run_all.py``.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ensure_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parent.parent / ".jax_cache")


def ensure_compile_cache() -> str | None:
    """Point jax's persistent compilation cache at its directory (see the
    module docstring) and return that directory — None where no cache is
    kept. Idempotent."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    if jax.default_backend() == "cpu":
        return None
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return DEFAULT_CACHE_DIR
