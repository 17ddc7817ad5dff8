"""Where XLA's persistent compilation cache lives.

A cold start of a 7B server compiles every program it serves from; the
persistent cache turns the second start into file reads.  The directory
is part of the cache key, so it must not move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself.  Nothing here
  sets another directory, so an operator (or a harness that keeps the
  directory between runs) decides where the cache lives.
- unset, on a TPU: one fixed, git-ignored directory at the root of the
  checkout, resolved from this package's location — never a temporary
  name, a pid or the time.
- unset, on any other backend: no cache.  CPU programs of the sizes that
  run there compile in seconds, and jaxlib 0.9.0 aborts the process when
  it reads back some multi-device CPU executables (seen with the cache on
  under tests/test_pipeline_decode.py), so a second start of a CPU server
  at tp > 1 must not find one.  Setting the variable on a CPU is the
  operator's own risk.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger("arks_tpu.compile_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache")


def configure() -> str | None:
    """Place the cache; call before the first compile and after the
    platform is chosen (this asks JAX for its backend).  Returns the
    directory in use, or None where there is none."""
    path = os.environ.get(ENV_VAR)
    if not path:
        import jax
        if jax.default_backend() != "tpu":
            log.info("persistent compilation cache: none on the %s backend "
                     "(%s not set)", jax.default_backend(), ENV_VAR)
            return None
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    log.info("persistent compilation cache: %s", path)
    return path
