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

import collections  # noqa: E402
import json  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _seeded_leaves_drawn_once():
    """``quant.init_params_quantized`` generates and quantises each leaf in
    a jit of the call's own, so every call compiles a program a leaf again:
    ~10 s for a routed preset on the CPU, at every engine, pod and stepper
    that takes int8 leaves.  A seed means one tree, so a worker draws each
    (configuration, key, dtype, bits, shards) once and every later caller
    gets the same arrays in containers of its own (the benchmark's pods
    too, whose files are not this suite's to edit).  A call that is being
    traced (``jax.eval_shape``) is passed through, and so is a tree over
    64 MiB (every preset's is a few)."""
    import numpy as np

    from arks_tpu.models import quant
    real, drawn = quant.init_params_quantized, {}

    def once(cfg, key, dtype=jax.numpy.bfloat16, bits=8, shards=1):
        try:
            at = (cfg, np.asarray(key).tobytes(), jax.numpy.dtype(dtype).name,
                  bits, shards)
            tree = drawn.get(at)
        except TypeError:        # a traced or typed key, an unhashable cfg
            return real(cfg, key, dtype, bits=bits, shards=shards)
        if tree is None:
            tree = real(cfg, key, dtype, bits=bits, shards=shards)
            leaves = jax.tree.leaves(tree)
            if (any(isinstance(x, jax.core.Tracer) for x in leaves)
                    or sum(x.nbytes for x in leaves) > 64 << 20):
                return tree      # traced; or no test size (kept by nobody)
            drawn[at] = tree
        return jax.tree.map(lambda x: x, tree)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(quant, "init_params_quantized", once)
        yield


@pytest.fixture(scope="session", autouse=True)
def _equal_programs_compiled_once():
    """Nine tenths of this suite's time is tracing and compiling (PR 50:
    108 of ``test_spec_decode.py``'s 158 s are XLA's back end), and most of
    it compiles a program the worker has compiled already: an engine's
    ``jax.jit``s are its own, so thirty engines of four configurations
    compile thirty times.  JAX's answer is its persistent cache, which this
    jaxlib cannot read back on the CPU (see the top of this file).  So a
    worker keeps the executables it has compiled, in memory and by what
    they are compiled from (the module's text without locations, the compile
    options, the device), and hands an equal request the same executable:
    nothing is serialised or outlives the process, and the compile event
    still fires (``xla_compilations_total`` counts as before).  Kept: the
    last 256 single-device CPU programs without host callbacks."""
    import hashlib
    import inspect

    import numpy as np
    from jax._src import compiler, dispatch
    real, kept = compiler.compile_or_get_cached, collections.OrderedDict()
    # ``compile_or_get_cached`` is private to JAX, and ``once`` takes its
    # first five operands by position: another JAX has to fail HERE.
    took = list(inspect.signature(real).parameters)[:5]
    assert took == ["backend", "computation", "devices", "compile_options",
                    "host_callbacks"], f"jax {jax.__version__} compiles " \
        f"through {took}: rewrite or remove _equal_programs_compiled_once"

    def once(backend, computation, devices, compile_options, host_callbacks,
             *rest, **kw):
        if host_callbacks or backend.platform != "cpu" or devices.size != 1:
            return real(backend, computation, devices, compile_options,
                        host_callbacks, *rest, **kw)
        at = (hashlib.sha256(computation.operation.get_asm(
            enable_debug_info=False).encode()).digest(),
            compile_options.SerializeAsString(), devices.flat[0].id)
        if at in kept:
            kept.move_to_end(at)
            return kept[at]
        kept[at] = real(backend, computation, devices, compile_options,
                        host_callbacks, *rest, **kw)
        if len(kept) > 256:
            kept.popitem(last=False)
        return kept[at]

    heard = []                       # compile events, while this listens

    def listener(event, seconds, **_):
        if heard is not None and event == dispatch.BACKEND_COMPILE_EVENT:
            heard.append(seconds)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compiler, "compile_or_get_cached", once)
        # Before any test runs: two ``jax.jit``s of one program are ONE
        # executable kept and TWO compile events (what the engine's
        # ``xla_compilations_total`` and the compile-budget tests count).
        jax.monitoring.register_event_duration_secs_listener(listener)
        for _ in range(2):
            jax.jit(lambda x: x * 3 + 1)(np.float32(2))
        assert (len(kept), len(heard)) == (1, 2), \
            f"jax {jax.__version__}: {len(kept)} executables kept and " \
            f"{len(heard)} compile events for two equal jits (1 and 2 " \
            "expected): rewrite or remove _equal_programs_compiled_once"
        heard = None
        yield


@pytest.fixture(scope="module")
def _registry_and_environment_restored():
    """``benchmarks/pod.py::build`` registers a configuration under its own
    name (a test size's is a preset's: ``tiny-mla-moe`` with half its experts
    held) and exports its deploy ``env`` (``ARKS_MIXED_CHUNK_TOKENS``), for
    the life of a benchmark process.  Here the process goes on to other test
    files (one xdist worker runs many: a later ``get_config("tiny-mla-moe")``
    or a step's chunking would read what a case here left), so both are put
    back when a file of benchmark cases is done (``pytestmark`` of
    ``tests/test_benchmark_contract.py`` and ``tests/test_contract_*.py``;
    module scope: set up before the imported
    ``served`` fixtures, which build pods, torn down after them)."""
    from arks_tpu.models import config
    registry, environ = dict(config._REGISTRY), dict(os.environ)
    yield
    config._REGISTRY.clear()
    config._REGISTRY.update(registry)
    os.environ.clear()
    os.environ.update(environ)


@pytest.fixture
def seeded_tree_as_drawn(monkeypatch):
    """The families' ``test_seeded_weights_are_the_programs_bit_for_bit``
    compare ``init_params_quantized``'s tree with the reference's leaf for
    leaf in the shape a leaf is DRAWN in, ``[L, E, H x D]`` (files of the
    benchmark: not every PR's to edit).  Since PR 48 the program stores the
    GQA stacks' q / k / v projections ``[L, H, D, E]`` (``tf.init_params``),
    since PR 57 the latent block's ``wq_b`` / ``wkv_b`` ``[.., H, D, K]``:
    the same numbers, transposed.  Those cases see the stored tree in the
    drawn order here; the stored order itself is held by
    ``tests/test_quant.py``."""
    import jax
    import harness
    from arks_tpu.models import quant
    stored = quant.init_params_quantized

    def drawn(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict) and not quant.is_quantized(leaf):
                out[name] = drawn(leaf)
            elif name in quant.LATENT_SPLIT_KEYS or (
                    name in quant.HEAD_SPLIT_KEYS
                    and jax.tree.leaves(leaf)[0].ndim == 4):
                out[name] = jax.tree.map(harness.as_drawn, leaf)
            else:
                out[name] = leaf
        return out

    monkeypatch.setattr(quant, "init_params_quantized",
                        lambda *a, **k: drawn(stored(*a, **k)))


@pytest.fixture(autouse=True)
def _arks_state_left_as_found(request):
    """A worker runs many files in one process: a test that leaves an
    ``ARKS_*`` variable or the preset registry changed (``benchmarks/pod.py
    ::build`` does both, for the life of a benchmark process) decides what a
    later file's engine reads.  It fails here, by name, and not there; what
    it left is put back either way.  The benchmark's own cases build pods in
    their bodies and are not this suite's to edit: a file that takes
    ``_registry_and_environment_restored`` is put back and not failed."""
    import harness
    found = harness.arks_state()
    yield
    left = harness.put_back(found)
    assert not left or "_registry_and_environment_restored" \
        in request.fixturenames, f"the test left changed: {left}"


# ``--dist loadfile`` hands files out in collection order, a file to the next
# free worker: in the alphabet's order the costliest files of its tail start
# last and five workers stand idle behind them (~110 s of a 1,200-s run, PR
# 50).  So the files go out costliest first, by the record the last whole run
# of the suite left (``pytest_terminal_summary`` below rewrites it; commit it
# with the PR that moved it).  A file the record does not know goes last.
_DURATIONS = os.path.join(os.path.dirname(__file__), "durations.json")
_SECONDS = collections.Counter()


def pytest_collection_modifyitems(items):
    try:
        with open(_DURATIONS) as f:
            cost = json.load(f)
    except (OSError, ValueError):
        return
    items.sort(key=lambda item: -cost.get(item.path.name, 0))


def pytest_runtest_logreport(report):
    _SECONDS[report.nodeid.split("::")[0]] += report.duration


def pytest_terminal_summary(terminalreporter, config):
    """The sum of every report's duration (set-up, call and tear-down: the
    CPU-seconds the run cost, whatever the number of workers) and the ten
    costliest files, in the driver's own log (ROADMAP D9).  A run of the
    whole suite as the driver selects it also leaves every file's
    CPU-seconds in ``tests/durations.json``, the next run's order."""
    if hasattr(config, "workerinput") or not _SECONDS:
        return
    terminalreporter.write_line(
        f"CPU-seconds of all reports: {sum(_SECONDS.values()):.0f}")
    for name, seconds in _SECONDS.most_common(10):
        terminalreporter.write_line(f"  {seconds:8.1f}  {name}")
    if (config.option.markexpr == "not slow" and not config.option.keyword
            and all(os.path.isdir(arg) for arg in config.args)):
        try:
            with open(_DURATIONS, "w") as f:
                json.dump({os.path.basename(name): round(seconds) for name,
                           seconds in _SECONDS.most_common()}, f, indent=0)
                f.write("\n")
        except OSError as e:             # a checkout that cannot be written
            terminalreporter.write_line(f"{_DURATIONS} not rewritten: {e}")
