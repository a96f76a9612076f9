"""JAX's persistent compilation cache for the repo's entry points.

A TPU run compiles every kernel and step program from cold unless the
compiled executables persist between processes. ``enable_compile_cache``
turns the cache on at ONE fixed place, because the cache directory is
part of what makes an entry hit:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; no other
    directory is set in code;
  * otherwise — ``<checkout>/.jax_cache`` (listed in ``.gitignore``),
    never a name built from a tempdir, a pid or the time.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/ingest.py``) call it first thing.
It does nothing off the TPU, so CPU runs and the tests never turn it on.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on for a TPU backend; returns the
    directory in use (None off the TPU)."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the filter kernels compile in well under the
    # default one-second threshold, and a chip call pays each of them cold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
