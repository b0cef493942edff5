"""The persistent XLA compilation cache shared by every launcher.

The layer stack is unrolled in Python, so a cold compile of the flagship
step is a large part of a short run; every entry point that compiles
(``chip_smoke.py``, ``benchmarks/run.py``, the
``python -m mpi4torch_tpu.*`` lanes) calls :func:`use_compile_cache`
before its first jit so that a second process finds the first one's
programs.  The directory is part of the cache key, so it is a fixed path:
never the cwd, a temp name, a pid or the time.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads the cache
    from there and nothing is set in code; otherwise the cache lives in
    ``<checkout>/.jax_cache``, computed from this file's own location.
    Call it before the first compilation of the process."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
