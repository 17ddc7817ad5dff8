"""arkslint self-tests: rule fixtures, call-graph behavior, the
hot-path acceptance diff against the legacy hand-curated tuple, and the
CLI / baseline / generated-docs contracts.

Fixtures are in-memory ``SourceTree`` dicts — the rules see no
difference from the on-disk tree, so each invariant gets a positive AND
a negative case without touching the real engine.
"""

import json
import pathlib
import resource
import subprocess
import sys

import pytest

from arks_tpu.analysis import SourceTree, repo_root, run_rules
from arks_tpu.analysis.baseline import MAX_SUPPRESSIONS, Baseline
from arks_tpu.analysis.callgraph import CallGraph
from arks_tpu.analysis.rules import hotpath as hotpath_rule


# ------------------------------------------------------------ call graph

def test_callgraph_direct_and_self_edges():
    tree = SourceTree({"arks_tpu/m.py": (
        "class C:\n"
        "    def a(self):\n"
        "        self.b()\n"
        "    def b(self):\n"
        "        pass\n"
        "    def c(self):\n"
        "        pass\n"
    )})
    g = CallGraph(tree)
    root = g.find("arks_tpu/m.py", "C", "a")
    reach = g.reachable([root])
    assert g.find("arks_tpu/m.py", "C", "b") in reach
    assert g.find("arks_tpu/m.py", "C", "c") not in reach


def test_callgraph_callback_reference_counts_as_edge():
    """``on_evict = self._note`` (no call parens) must still pull the
    callback into the reachable set — the scheduler registers hot-path
    callbacks exactly this way."""
    tree = SourceTree({"arks_tpu/m.py": (
        "class C:\n"
        "    def a(self):\n"
        "        self.alloc.on_evict = self._note\n"
        "    def _note(self):\n"
        "        pass\n"
    )})
    g = CallGraph(tree)
    reach = g.reachable([g.find("arks_tpu/m.py", "C", "a")])
    assert g.find("arks_tpu/m.py", "C", "_note") in reach


def test_callgraph_cross_module_edges():
    tree = SourceTree({
        "arks_tpu/a.py": (
            "from arks_tpu.b import helper\n"
            "from arks_tpu import c\n"
            "def top():\n"
            "    helper()\n"
            "    c.other()\n"
        ),
        "arks_tpu/b.py": "def helper():\n    pass\n",
        "arks_tpu/c.py": "def other():\n    pass\n",
    })
    g = CallGraph(tree)
    reach = g.reachable([g.find("arks_tpu/a.py", None, "top")])
    assert g.find("arks_tpu/b.py", None, "helper") in reach
    assert g.find("arks_tpu/c.py", None, "other") in reach


def test_callgraph_boundary_stops_propagation():
    tree = SourceTree({"arks_tpu/m.py": (
        "class C:\n"
        "    def a(self):\n"
        "        self._resolve_x()\n"
        "    def _resolve_x(self):\n"
        "        self.deep()\n"
        "    def deep(self):\n"
        "        pass\n"
    )})
    g = CallGraph(tree)
    reach = g.reachable(
        [g.find("arks_tpu/m.py", "C", "a")],
        stop=lambda fn: fn.name.startswith("_resolve_"))
    assert g.find("arks_tpu/m.py", "C", "_resolve_x") not in reach
    assert g.find("arks_tpu/m.py", "C", "deep") not in reach


# -------------------------------------------------------- hotpath fixtures

_ENGINE_FIXTURE = {
    "arks_tpu/engine/engine.py": (
        "import time\n"
        "import numpy as np\n"
        "class InferenceEngine:\n"
        "    def step(self):\n"
        "        self._issue()\n"
        "        self._resolve_decode()\n"
        "        self.alloc.on_evict = self._cb\n"
        "    def _issue(self):\n"
        "        return np.asarray(self.buf)\n"
        "    def _cb(self):\n"
        "        time.sleep(0.1)\n"
        "    def _resolve_decode(self):\n"
        "        return np.asarray(self.out)\n"
        "    def _unreached(self):\n"
        "        return np.asarray(self.other)\n"
    ),
}


def test_hotpath_flags_reachable_fetch_not_tails_or_unreached():
    findings = run_rules(SourceTree(_ENGINE_FIXTURE), ["hotpath"])
    fetches = {f.qualname for f in findings if f.check == "blocking-fetch"}
    assert "InferenceEngine._issue" in fetches
    assert "InferenceEngine._resolve_decode" not in fetches
    assert "InferenceEngine._unreached" not in fetches


def test_hotpath_follows_callback_registration():
    findings = run_rules(SourceTree(_ENGINE_FIXTURE), ["hotpath"])
    sleeps = {f.qualname for f in findings if f.check == "serialization"}
    assert "InferenceEngine._cb" in sleeps


def test_hotpath_contract_flags_missing_tails():
    findings = run_rules(SourceTree(_ENGINE_FIXTURE), ["hotpath"])
    contract = {f.qualname for f in findings if f.check == "contract"}
    # the fixture has neither _step_pipelined nor the sync tails
    assert "InferenceEngine._step_pipelined" in contract
    assert any(q.endswith("._resolve_mixed") for q in contract)


_SEQ_STEP_FIXTURE = {
    "arks_tpu/engine/engine.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "class InferenceEngine:\n"
        "    def _issue_x(self, tag):\n"
        "        self.trace.evt('', tag + 'dispatch', 'B')\n"
        "        self._pack()\n"
        "        return self._fn(np.zeros(3))\n"
        "    def _resolve_x(self, tag):\n"
        "        self.trace.evt('', tag + 'wait', 'B')\n"
        "        self._promote()\n"
        "    def _pack(self):\n"
        "        return jnp.asarray(self.buf)\n"
        "    def _promote(self):\n"
        "        k = jax.random.fold_in(self.key, 1)\n"
        "        return jax.device_put(k), jnp.array([1])\n"
        "    def _admit(self):\n"
        "        return jnp.asarray(self.other)\n"
    ),
}


@pytest.mark.parametrize("qualname,detail", [
    ("InferenceEngine._pack", "jnp.asarray(self.buf)"),
    ("InferenceEngine._promote", "jax.random.fold_in(self.key, 1)"),
    ("InferenceEngine._promote", "jax.device_put(k)"),
    ("InferenceEngine._promote", "jnp.array([1])"),
])
def test_hotpath_flags_eager_device_calls_between_wait_and_dispatch(
        qualname, detail):
    """Roots are the methods that open a ``wait`` / ``dispatch`` section —
    the resolve tail included; what they reach may not call into JAX
    eagerly."""
    findings = run_rules(SourceTree(_SEQ_STEP_FIXTURE), ["hotpath"])
    eager = {(f.qualname, f.detail) for f in findings
             if f.check == "eager-device-call"}
    assert (qualname, detail) in eager
    assert not any(q == "InferenceEngine._admit" for q, _ in eager)


def test_hotpath_eager_check_reports_lost_roots():
    findings = run_rules(SourceTree(_ENGINE_FIXTURE), ["hotpath"])
    lost = [f.message for f in findings if f.check == "contract"
            and "eager-device-call has lost its roots" in f.message]
    assert len(lost) == 2          # neither a wait nor a dispatch section


_DELIVERY_FIXTURE = {
    "arks_tpu/engine/engine.py": (
        "class _Gate:\n"
        "    def put(self, out):\n"
        "        self._inner.put(out)\n"
        "class InferenceEngine:\n"
        "    def _deliver(self, req, out):\n"
        "        req.outputs.put(out)\n"
        "    def _flush_deferred(self):\n"
        "        for req, out in self._deferred:\n"
        "            req.outputs.put(out)\n"
        "    def _finish(self, st):\n"
        "        self._deliver(st.request, 1)\n"
        "    def _abort(self, st):\n"
        "        st.request.outputs.put(2)\n"
        "        self._queue.put(st)\n"
    ),
}


def test_hotpath_flags_an_output_put_outside_the_delivery_helpers():
    findings = run_rules(SourceTree(_DELIVERY_FIXTURE), ["hotpath"])
    direct = [(f.line, f.detail) for f in findings
              if f.check == "direct-output-put"]
    # the helpers' own puts, a gate's inner queue and the admission queue
    # are no findings; the one that goes round the helper is
    assert direct == [(13, "st.request.outputs.put")]
    # ... and an engine without the helpers has lost its one door
    lost = [f.qualname for f in run_rules(SourceTree(_ENGINE_FIXTURE),
                                          ["hotpath"])
            if f.check == "contract" and "direct-output-put" in f.message]
    assert lost == ["InferenceEngine._deliver",
                    "InferenceEngine._flush_deferred"]


# ----------------------------------------------------------- acceptance

# The hand-curated allowlist the analyzer replaced (tests/
# test_hotpath_guard.py at its last hand-maintained revision).  The
# call-graph discovery must cover every one of these WITHOUT any of them
# being listed in the rule — if a rename breaks an edge, this diff test
# names exactly the function that fell out of coverage.
LEGACY_HOT_PATH_FUNCTIONS = (
    "step", "_step_pipelined", "_pipe_issue", "_issue_decode",
    "_issue_mixed", "_issue_spec_mixed", "_fill_chunk_lanes",
    "_issue_admit_batch", "_spill_flush", "_issue_restore",
    "_dispatch_restore_group", "_issue_model_load", "_park_awaiting_model",
    "_note_evicted", "_register_prompt_pages", "_maybe_preempt",
    "_issue_preempt_swap", "_preempt_replay", "_service_swapped",
    "_resume_swapped", "_mixed_grid_counters",
)


def test_step_reachability_covers_legacy_hot_path_tuple():
    tree = SourceTree.load(repo_root())
    graph = CallGraph(tree)
    reach = hotpath_rule.step_reachable(graph)
    names = {graph.nodes[nid].name for nid in reach
             if graph.nodes[nid].path == hotpath_rule.ENGINE}
    missing = [n for n in LEGACY_HOT_PATH_FUNCTIONS if n not in names]
    assert not missing, (
        f"call-graph discovery lost legacy hot-path coverage: {missing}")
    # and it genuinely discovers MORE than the hand-list ever did
    assert len(names) > len(LEGACY_HOT_PATH_FUNCTIONS)


def test_rule_source_hand_lists_no_hot_path_helper():
    """The rule must keep discovering the hot path, not enumerate it:
    none of the legacy names (beyond the two roots) may appear in the
    rule's source."""
    src = pathlib.Path(hotpath_rule.__file__).read_text()
    roots = {"step", "_step_pipelined"}
    listed = [n for n in LEGACY_HOT_PATH_FUNCTIONS
              if n not in roots and f'"{n}"' in src]
    assert not listed, f"hand-listed hot-path names crept back in: {listed}"


# ------------------------------------------------------ exceptions fixtures

def test_exceptions_engine_strict_vs_repo_lenient():
    lenient = (
        "import logging\n"
        "log = logging.getLogger()\n"
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        log.exception('boom')\n"
    )
    tree = SourceTree({"arks_tpu/engine/x.py": lenient,
                       "arks_tpu/gateway/x.py": lenient})
    findings = run_rules(tree, ["exceptions"])
    paths = {f.path for f in findings}
    # log.exception is an observable swallow outside the engine only
    assert "arks_tpu/engine/x.py" in paths
    assert "arks_tpu/gateway/x.py" not in paths


def test_exceptions_fault_api_and_narrow_handlers_pass():
    tree = SourceTree({"arks_tpu/engine/x.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as e:\n"
        "        swallowed('site', e)\n"
        "    try:\n"
        "        g()\n"
        "    except ValueError:\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        raise\n"
    )})
    assert not run_rules(tree, ["exceptions"])


def test_exceptions_flags_bare_swallow():
    tree = SourceTree({"arks_tpu/control/x.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
    )})
    findings = run_rules(tree, ["exceptions"])
    assert [f.check for f in findings] == ["broad-swallow"]


# ----------------------------------------------------------- knobs fixtures

_REGISTRY_FIXTURE = (
    "def _k(*a, **kw):\n"
    "    pass\n"
    '_k("ARKS_GOOD", "int", "4", "doc", "engine")\n'
)


def _knob_tree(body: str) -> SourceTree:
    return SourceTree({
        "arks_tpu/utils/knobs.py": _REGISTRY_FIXTURE,
        "arks_tpu/x.py": body,
    })


def test_knobs_flags_raw_env_read_and_write():
    findings = run_rules(_knob_tree(
        "import os\n"
        'a = os.environ.get("ARKS_GOOD", "4")\n'
        'os.environ["ARKS_GOOD"] = "5"\n'
    ), ["knobs"])
    checks = sorted(f.check for f in findings if f.severity == "error")
    assert checks == ["raw-env-read", "raw-env-write"]


def test_knobs_accessor_with_registered_name_passes():
    findings = run_rules(_knob_tree(
        "from arks_tpu.utils import knobs\n"
        'a = knobs.get_int("ARKS_GOOD")\n'
    ), ["knobs"])
    assert not [f for f in findings if f.severity == "error"]


def test_knobs_flags_unregistered_name():
    findings = run_rules(_knob_tree(
        "from arks_tpu.utils import knobs\n"
        'a = knobs.get_int("ARKS_NOPE")\n'
    ), ["knobs"])
    assert "unregistered-knob" in {f.check for f in findings}


def test_knobs_module_constant_resolves_statically():
    findings = run_rules(_knob_tree(
        "from arks_tpu.utils import knobs\n"
        'ENV = "ARKS_GOOD"\n'
        "def f():\n"
        "    return knobs.get_int(ENV)\n"
    ), ["knobs"])
    assert "dynamic-knob-name" not in {f.check for f in findings}


def test_knobs_dynamic_name_warns():
    findings = run_rules(_knob_tree(
        "from arks_tpu.utils import knobs\n"
        "def f(name):\n"
        "    return knobs.get_int(name)\n"
    ), ["knobs"])
    dyn = [f for f in findings if f.check == "dynamic-knob-name"]
    assert dyn and all(f.severity == "warn" for f in dyn)


def test_knobs_unused_registration_warns():
    findings = run_rules(SourceTree({
        "arks_tpu/utils/knobs.py": _REGISTRY_FIXTURE,
    }), ["knobs"])
    unused = [f for f in findings if f.check == "unused-knob"]
    assert [f.detail for f in unused] == ["ARKS_GOOD"]
    assert all(f.severity == "warn" for f in unused)


# ------------------------------------------------------ tracepurity fixtures

def test_tracepurity_flags_host_state_in_traced_functions():
    findings = run_rules(SourceTree({"arks_tpu/ops/x.py": (
        "import time, os\n"
        "import jax\n"
        "@jax.jit\n"
        "def traced(x):\n"
        "    t = time.time()\n"
        '    e = os.environ.get("ARKS_GOOD")\n'
        "    return x\n"
        "def kernel(ref):\n"
        "    import numpy as np\n"
        "    return np.random.rand()\n"
        "def launch():\n"
        "    return pl.pallas_call(kernel)\n"
        "def untraced():\n"
        "    return time.time()\n"
    )}), ["tracepurity"])
    by_fn = {}
    for f in findings:
        by_fn.setdefault(f.qualname, set()).add(f.check)
    assert by_fn.get("traced") == {"wall-clock", "host-state"}
    assert by_fn.get("kernel") == {"host-rng"}
    assert "untraced" not in by_fn


# --------------------------------------------------------- metrics fixtures

def test_metrics_conventions_and_duplicates():
    findings = run_rules(SourceTree({
        "arks_tpu/a.py": (
            "class AMetrics:\n"
            "    def __init__(self, reg):\n"
            '        self.c = reg.counter("requests_total", "d")\n'
            '        self.bad = reg.counter("requests_seconds", "d")\n'
            '        self.g = reg.gauge("depth_total", "d")\n'
        ),
        "arks_tpu/b.py": (
            "class BMetrics:\n"
            "    def __init__(self, reg):\n"
            '        self.c = reg.counter("requests_total", "d")\n'
        ),
    }), ["metrics"])
    checks = sorted(f.check for f in findings)
    assert checks.count("duplicate-family") == 1
    # counter without _total AND gauge with _total are both conventions
    assert checks.count("name-convention") == 2


# ----------------------------------------------------- CLI / baseline / docs

def test_cli_exits_zero_on_the_real_tree_under_ten_seconds():
    # The budget is the tool's own work: its process's CPU seconds, which a
    # loaded machine does not stretch (its wall time read 11.3 s beside five
    # busy workers, PR 50; the wall has a margin of ten, the timeout).
    def cpu():
        r = resource.getrusage(resource.RUSAGE_CHILDREN)
        return r.ru_utime + r.ru_stime
    c0 = cpu()
    proc = subprocess.run(
        [sys.executable, "-m", "arks_tpu.analysis", "--all", "--json"],
        cwd=repo_root(), capture_output=True, text=True, timeout=100)
    elapsed = cpu() - c0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert elapsed < 10, f"arkslint took {elapsed:.1f}s of CPU (budget 10s)"
    payload = json.loads(proc.stdout)
    assert payload["counts"]["errors"] == 0
    assert payload["counts"]["stale"] == 0


def test_baseline_is_reviewed_and_bounded():
    baseline = Baseline.load(
        repo_root() / "tools" / "arkslint-baseline.json")
    assert baseline.entries, "baseline file went missing"
    assert len(baseline.entries) <= MAX_SUPPRESSIONS
    for e in baseline.entries:
        assert e["reason"] and "TODO" not in e["reason"], e


def test_baseline_has_no_stale_entries():
    findings = run_rules(SourceTree.load(repo_root()))
    baseline = Baseline.load(
        repo_root() / "tools" / "arkslint-baseline.json")
    _active, _suppressed, stale = baseline.apply(findings)
    assert not stale, f"stale suppressions: {stale}"


def test_generated_knob_docs_are_in_sync():
    """docs/configuration.md is generated (``--gen-knob-docs``); a knob
    edit without regeneration fails here, not in review."""
    from arks_tpu.utils import knobs
    on_disk = (repo_root() / "docs" / "configuration.md").read_text()
    assert on_disk == knobs.render_markdown(), (
        "docs/configuration.md is stale — run "
        "python -m arks_tpu.analysis --gen-knob-docs")
