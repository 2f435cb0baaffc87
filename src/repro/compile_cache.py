"""Where compiled programs and kernel tunings persist between runs.

JAX's persistent compilation cache keys entries on, among other things,
the directory they live in, so the directory has to be fixed for a later
run to hit.  :func:`enable_compile_cache` is called once at the top of
each entry point (``serve_datalog``, ``benchmarks.run``,
``chip_smoke.py``) and never on import:

* when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and
  the helper sets no other directory;
* otherwise it points JAX at :data:`CACHE_DIR`, ``.jax_cache`` at the
  root of the checkout (listed in ``.gitignore``).

The Pallas autotuner keeps its table in :data:`CACHE_DIR` too
(:func:`repro.kernels.tune.cache_path`).
"""

from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "ENV_VAR", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/...``)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
