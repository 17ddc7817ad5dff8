"""Test env: force an 8-device virtual CPU mesh.

Mirrors the driver's multi-chip dry-run environment; all sharding tests run
against this mesh, never real TPU hardware (the platform is forced here, so
the suite cannot take a chip even where one is attached).
"""

import os

# Pipelined decoding stays opt-in per test: at the production default
# (depth 2) every engine that reaches steady state kicks a background
# compile of both pipe-program variants, loading the CPU under the whole
# suite for no extra coverage — token streams are depth-invariant by
# contract, and tests/test_pipeline_decode.py asserts depths 1-3
# explicitly (its engines set this env themselves).
os.environ.setdefault("ARKS_PIPELINE_DEPTH", "0")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The program places no persistent compilation cache on the CPU
# (arks_tpu.utils.compile_cache), so the suite runs as a CPU server ships.
# This guards the one way left to get one, a JAX_COMPILATION_CACHE_DIR
# inherited from the caller's environment: a run must not depend on what an
# earlier run left on disk, and jaxlib 0.9.0 aborts the process when it
# deserializes some multi-device CPU executables (seen in
# tests/test_pipeline_decode.py with the cache on).
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)
