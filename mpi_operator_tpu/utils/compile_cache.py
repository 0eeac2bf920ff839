"""Where the persistent XLA compilation cache lives.

Every chip session starts with no compiled code, and compiling the
GPT-2-medium train step or the serving engine's programs is a large part
of a cold run. jax can keep executables on disk between processes; the
directory is part of the cache key, so it must not move between runs.

Contract (one function, called by every data-plane entry point that
compiles, before its first trace — never at `import mpi_operator_tpu`,
so library users and the in-process tests are unaffected):

  JAX_COMPILATION_CACHE_DIR set   → jax already honours it; nothing is
                                    set here.
  not set, accelerator backend    → `<checkout>/.jax_compile_cache`, fixed
                                    by the package's location (listed in
                                    .gitignore and .chiprunignore).
  not set, CPU backend            → no cache. XLA:CPU reloads its own
                                    ahead-of-time results with a
                                    machine-feature mismatch error per
                                    program (jaxlib 0.9.0, this image) and
                                    CPU compiles are seconds; the cache is
                                    for the chip.

Call it after `bootstrap.initialize()`: it reads the backend, and
`jax.distributed.initialize` must run before any backend exists.
"""
from __future__ import annotations

import os
from typing import Optional

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory the compile cache uses: the environment's when it
    names one, else the fixed path inside the checkout."""
    return os.environ.get(ENV_CACHE_DIR) or os.path.join(
        _CHECKOUT, ".jax_compile_cache")


def enable_compile_cache() -> Optional[str]:
    """Make jax's persistent compilation cache use `compile_cache_dir()`;
    returns the directory in use, or None when no cache is on (CPU with
    nothing in the environment). Sets nothing in jax's config when the
    environment already placed the cache."""
    path = compile_cache_dir()
    if os.environ.get(ENV_CACHE_DIR):
        return path
    import jax

    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["ENV_CACHE_DIR", "compile_cache_dir", "enable_compile_cache"]
