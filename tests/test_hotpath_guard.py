"""Static guard over the decode hot path — thin wrapper over arkslint.

The invariants this file used to implement by hand (zero-host-sync issue
path, autotune-sweep containment, evt-only tracing, jax-free sketch
module) now live in ``arks_tpu/analysis/rules/hotpath.py``, which
discovers the issue-side hot path by CALL GRAPH from the scheduler roots
instead of the hand-curated ``HOT_PATH_FUNCTIONS`` tuple this file used
to carry — a new helper cannot dodge the guard by not being listed.
Reviewed exceptions (the old ``ALLOWED`` set) live in
``tools/arkslint-baseline.json`` with one-line justifications.

These wrappers keep ``pytest tests/`` and ``python -m arks_tpu.analysis``
two doors into the same checker: each test filters the rule's findings
by sub-check so a regression still fails the test whose name says what
broke.  The call-graph discovery itself (including the guarantee that it
covers everything the legacy tuple listed) is tested in
``tests/test_analysis.py``.
"""

import functools

from arks_tpu.analysis import SourceTree, repo_root, run_rules
from arks_tpu.analysis.baseline import Baseline


@functools.lru_cache(maxsize=1)
def _active_findings():
    """hotpath findings over the real tree, baseline applied (staleness
    is asserted by test_analysis.py / the CLI, not per-wrapper)."""
    root = repo_root()
    findings = run_rules(SourceTree.load(root), ["hotpath"])
    baseline = Baseline.load(root / "tools" / "arkslint-baseline.json")
    active, _suppressed, _stale = baseline.apply(findings)
    return [f for f in active if f.severity == "error"]


def _errors(*checks):
    return [f.render() for f in _active_findings() if f.check in checks]


def test_no_blocking_fetches_on_the_issue_path():
    assert not _errors("blocking-fetch"), _errors("blocking-fetch")


def test_no_eager_device_call_between_wait_and_dispatch():
    """A sequential step's host values are operands of its one program:
    no ``jnp.asarray`` / ``jnp.array`` / ``jax.device_put`` /
    ``jax.random.*`` reachable from the functions that open its ``wait``
    and ``dispatch`` sections."""
    assert not _errors("eager-device-call"), _errors("eager-device-call")


def test_no_output_put_outside_the_delivery_helper():
    """Every frame of ``engine.py`` reaches its reader through
    ``_deliver`` (or leaves a deferral through ``_flush_deferred``): a
    direct ``.outputs.put(`` could overtake a deferred frame."""
    assert not _errors("direct-output-put"), _errors("direct-output-put")


def test_no_sweep_reachable_from_step_loop():
    assert not _errors("autotune-sweep"), _errors("autotune-sweep")


def test_no_serialization_on_the_issue_path():
    assert not _errors("serialization", "lock-acquire"), (
        _errors("serialization", "lock-acquire"))


def test_trace_calls_on_hot_path_are_evt_only():
    assert not _errors("trace-access"), _errors("trace-access")


def test_tracer_evt_is_lock_and_serialization_free():
    assert not _errors("trace-evt-impl"), _errors("trace-evt-impl")


def test_sketch_module_stays_jax_free():
    assert not _errors("sketch-import"), _errors("sketch-import")


def test_resolve_tails_exist():
    """Roots and sanctioned host-sync tails still exist under their
    expected names — the guard is only meaningful while they do."""
    assert not _errors("contract"), _errors("contract")
