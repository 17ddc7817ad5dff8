"""Rule ``hotpath`` — zero-host-sync purity of the issue-side hot path.

The pipelined scheduler's contract: one dispatch goes OUT per issue-side
call, nothing comes back.  Instead of a hand-curated function allowlist
(the old ``HOT_PATH_FUNCTIONS`` tuple in tests/test_hotpath_guard.py,
which every PR had to remember to extend), this rule propagates the
contract over the call graph from the scheduler roots — every function
transitively reachable from ``step`` / ``_step_pipelined`` on the issue
side is checked automatically, so a new helper cannot dodge the guard by
not being listed.

Sanctioned boundaries (excluded from propagation, each with its own
contract):

- ``_resolve_*`` / ``_pipe_resolve_*`` / ``_finish_resume`` — the host-
  sync tails where blocking fetches BELONG;
- ``_warm_autotune`` — the pre-first-dispatch warm-up, the one place
  allowed to call ``autotune.ensure/sweep``;
- ``_disk_write_loop`` / ``_fetch_loop`` — the tier-2 spill writer and
  prefix-fetch worker THREADS (reached via their Thread-target
  registration): file and peer-HTTP IO is their whole job, so the
  issue-side purity contract stops at the thread hand-off queue;
- ``_residency_step`` — the windowed-residency forward: engagement
  spills, span-chained attends, and the sampler tail resolve
  synchronously by contract (a windowed slot's context does not fit the
  device, so its step IS a host-sync round trip).  Its prefetch issue
  helpers re-enter the checked set as explicit ROOTS instead.

(``_switch_to`` is deliberately NOT a boundary even though its stall is
sanctioned — it runs only after ``_drained_for_switch()`` — because its
subtree (``_init_model_state``) is where hot-path callbacks like
``on_evict -> _note_evicted`` are registered; cutting it off would blind
the graph to them.  Its one intentional finding, the warm-autotune call,
carries a baseline entry instead.)

Checks per reachable function:

- ``blocking-fetch``   np.asarray / device_get / .block_until_ready /
                       .item outside the sync tails;
- ``autotune-sweep``   a compile-and-time sweep reachable from the step
                       loop (``autotune.sweep`` / ``autotune.ensure`` /
                       ``_warm_autotune``);
- ``trace-access``     tracer use other than ``self.trace.evt`` /
                       ``.enabled`` (trace assembly leaking onto the
                       issue path);
- ``serialization``    time.sleep / json or pickle (de)serialization;
- ``lock-with``        WARN: ``with <...lock/mutex...>`` — brief host
                       mutexes are idiomatic here, but every new one
                       should be seen in review;
- ``lock-acquire``     explicit ``.acquire()`` (unbounded block).

One more check runs over its own, narrower reachable set — what the
engine thread does between a sequential step's ``wait`` and the next
``dispatch``.  Its roots are discovered, not listed: the engine methods
that open a ``wait`` or a ``dispatch`` step section (``tag + "wait"`` —
the mixed and spec-mixed issue and resolve functions; the resolve tails
are INCLUDED here, since fan-out and promotion live in them):

- ``eager-device-call``  ``jnp.asarray`` / ``jnp.array`` /
                       ``jax.device_put`` / ``jax.random.*`` outside a
                       jitted function: each is a call into JAX of its
                       own (a transfer, or a tiny program) that drops and
                       retakes the GIL while the device idles.  A step's
                       host values are operands of its ONE program
                       (``_OperandPack``); what a program needs computed
                       is computed inside it.

And one over the whole engine file:

- ``direct-output-put``  ``<request>.outputs.put(...)`` in
                       ``arks_tpu/engine/engine.py`` outside the delivery
                       helpers (``_deliver``, ``_flush_deferred``).  A
                       saturated resolve keeps its frames back until the
                       next dispatch is out; a put that went round the
                       helper could overtake an earlier frame of its
                       request (a token after its ``finished``, an abort
                       before the tokens it ends).

Plus three surface contracts the old guard carried: ``trace-evt-impl``
(``Tracer.evt`` / ``_Ring`` stay lock- and serialization-free),
``sketch-import`` (``prefix_sketch`` stays importable without jax or the
engine), and ``contract`` (roots and sanctioned sync tails still exist
under their expected names).
"""

from __future__ import annotations

import ast
import re

from arks_tpu.analysis import Finding, SourceTree
from arks_tpu.analysis import queries as q
from arks_tpu.analysis.callgraph import CallGraph

RULE = "hotpath"

ENGINE = "arks_tpu/engine/engine.py"
ENGINE_CLASS = "InferenceEngine"

# Scheduler roots: the two step entry points, the sketch-export surface
# (server threads, same non-blocking contract), and the weight-streaming
# scatter path (H2D puts overlapped with live decode).
ROOTS = (
    (ENGINE, ENGINE_CLASS, "step"),
    (ENGINE, ENGINE_CLASS, "_step_pipelined"),
    (ENGINE, ENGINE_CLASS, "cache_sketch"),
    (ENGINE, ENGINE_CLASS, "note_prompt_text"),
    ("arks_tpu/models/weights.py", None, "stream_params_to_device"),
    # Tenant-fair admission: the WDRR pick/put/aging path runs inside the
    # scheduler's admission slice every step — same no-serialization /
    # no-sleep / no-blocking-fetch contract as the step roots.  (Appended
    # AFTER the legacy entries: step_reachable slices ROOTS[:2].)
    ("arks_tpu/engine/fairqueue.py", "FairQueue", "get_nowait"),
    ("arks_tpu/engine/fairqueue.py", "FairQueue", "put"),
    ("arks_tpu/engine/fairqueue.py", "FairQueue", "head_prio"),
    ("arks_tpu/engine/fairqueue.py", "FairQueue", "age_tick"),
    # Fleet prefix KV (tier 2): the spill hand-off and fetch park run in
    # the scheduler's step slice (file IO lives on the writer/fetch
    # threads — only the queue hand-off is issue-side); block_for_export
    # serves peer GETs from server threads under the same non-blocking
    # contract as cache_sketch; the disk tier's admission probe is a
    # pure in-memory index walk.
    (ENGINE, ENGINE_CLASS, "_drain_disk_spills"),
    (ENGINE, ENGINE_CLASS, "_issue_fetch"),
    (ENGINE, ENGINE_CLASS, "block_for_export"),
    ("arks_tpu/engine/prefix_cache.py", "DiskPrefixTier", "match_digests"),
    # Windowed residency (contexts larger than the device pool): the
    # prefetch ISSUE helpers — staging-half H2D scatter and span-table
    # assembly — run between attend dispatches inside the residency
    # forward; if they ever block on the device, the span-(i+1) prefetch
    # stops overlapping the attend of span i that hides it.  The forward
    # itself resolves logits synchronously by contract, so
    # _residency_step is a sanctioned sync tail (BOUNDARY_RE below),
    # like the _resolve_* family.
    ("arks_tpu/engine/residency.py", "ResidencyManager", "_ensure_staged"),
    ("arks_tpu/engine/residency.py", "ResidencyManager", "_span_tables"),
    # Elastic resize: the reshard plan builds per-leaf device_put calls
    # from live params at the drained boundary — issue-side by design
    # (survivors are parked on host; a blocking fetch here stretches the
    # drain window every in-flight stream is waiting out).  The warm-up
    # issue helper runs right after the rebuild on the scheduler thread,
    # before traffic returns — same no-sleep / no-serialization budget.
    ("arks_tpu/models/weights.py", None, "reshard_params_to_mesh"),
    (ENGINE, ENGINE_CLASS, "_issue_warmup_request"),
)

BOUNDARY_RE = re.compile(
    r"^(_resolve_|_pipe_resolve_)"
    r"|^(_finish_resume|_warm_autotune|_disk_write_loop|_fetch_loop"
    r"|_residency_step)$")

# The sanctioned host-sync tails the boundary regex exists FOR: if these
# disappear wholesale the guard is checking a fiction.
EXPECTED_TAILS = (
    "_resolve_decode", "_resolve_mixed", "_resolve_spec_mixed",
    "_pipe_resolve_one", "_resolve_admit_batch", "_resolve_spills",
    "_resolve_restores", "_resolve_preempt_swaps", "_finish_resume",
    "_resolve_fetches", "_disk_write_loop", "_fetch_loop",
    "_residency_step",
)

# The step sections whose functions root ``eager-device-call``.
SEQ_STEP_SECTIONS = ("wait", "dispatch")

# The only engine methods that may put into a request's ``outputs``.
DELIVERY_HELPERS = ("_deliver", "_flush_deferred")

SERIAL_CALLS = {"json.dumps", "json.loads", "pickle.dumps",
                "pickle.loads", "pickle.dump", "pickle.load",
                "time.sleep", "marshal.dumps", "marshal.loads"}

_LOCKISH = re.compile(r"lock|mutex|condition|semaphore", re.I)


def step_reachable(graph: CallGraph) -> set[str]:
    """Issue-side reachable set from the two scheduler step roots only
    (the acceptance-test surface: must cover the legacy tuple)."""
    roots = [graph.find(*r) for r in ROOTS[:2]]
    return graph.reachable([r for r in roots if r],
                           stop=lambda fn: bool(BOUNDARY_RE.match(fn.name)))


def _function_findings(fn, findings: list[Finding]) -> None:
    path, qual = fn.path, (f"{fn.cls}.{fn.name}" if fn.cls else fn.name)
    for hit, arg, lineno in q.blocking_fetches(fn.node):
        findings.append(Finding(
            RULE, "blocking-fetch", path, lineno, qual,
            "blocking device fetch on the issue-side hot path (move it "
            "into a _resolve_* tail or add a reviewed baseline entry)",
            detail=f"{hit}({arg})"))
    for node in ast.walk(fn.node):
        if isinstance(node, ast.Call) and isinstance(node.func,
                                                     ast.Attribute):
            f = node.func
            recv = ast.unparse(f.value)
            full = f"{recv}.{f.attr}"
            if f.attr in ("sweep", "ensure") \
                    and recv.split(".")[-1] == "autotune":
                findings.append(Finding(
                    RULE, "autotune-sweep", path, node.lineno, qual,
                    "autotune sweep reachable from the step loop (only "
                    "_warm_autotune may compile-and-time candidates)",
                    detail=full))
            elif f.attr == "_warm_autotune":
                findings.append(Finding(
                    RULE, "autotune-sweep", path, node.lineno, qual,
                    "warm-up sweep called from the step loop",
                    detail=full))
            elif full in SERIAL_CALLS:
                findings.append(Finding(
                    RULE, "serialization", path, node.lineno, qual,
                    "serialization/sleep on the issue-side hot path",
                    detail=full))
            elif f.attr == "acquire" and _LOCKISH.search(recv):
                # only lock-like receivers: pool/guide refcount
                # .acquire() is bookkeeping, not an unbounded block
                findings.append(Finding(
                    RULE, "lock-acquire", path, node.lineno, qual,
                    "explicit lock acquire on the issue-side hot path",
                    detail=full))
        if isinstance(node, ast.Attribute):
            v = node.value
            if (isinstance(v, ast.Attribute) and v.attr == "trace"
                    and isinstance(v.value, ast.Name)
                    and v.value.id == "self"
                    and node.attr not in ("evt", "enabled")):
                findings.append(Finding(
                    RULE, "trace-access", path, node.lineno, qual,
                    "non-evt tracer access on the issue-side hot path "
                    "(trace assembly belongs off-thread)",
                    detail=f"self.trace.{node.attr}"))
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                expr = ast.unparse(item.context_expr)
                if _LOCKISH.search(expr):
                    findings.append(Finding(
                        RULE, "lock-with", path, node.lineno, qual,
                        "lock held on the issue-side hot path (keep the "
                        "critical section bounded and host-only)",
                        detail=expr, severity="warn"))


def _seq_step_roots(methods: dict) -> dict[str, set[str]]:
    """Section -> the engine methods that open it (``tag + "<section>"``
    as a step-section span's name)."""
    out: dict[str, set[str]] = {sec: set() for sec in SEQ_STEP_SECTIONS}
    for name, node in methods.items():
        for sub in ast.walk(node):
            if (isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Add)
                    and isinstance(sub.right, ast.Constant)
                    and sub.right.value in out):
                out[sub.right.value].add(name)
    return out


def _eager_device_calls(fn, findings: list[Finding]) -> None:
    qual = f"{fn.cls}.{fn.name}" if fn.cls else fn.name
    for node in ast.walk(fn.node):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        full = ast.unparse(node.func)
        if (full in ("jnp.asarray", "jnp.array", "jax.device_put")
                or full.startswith("jax.random.")):
            findings.append(Finding(
                RULE, "eager-device-call", fn.path, node.lineno, qual,
                "eager call into JAX between a sequential step's wait and "
                "the next dispatch (make the value an operand of the "
                "step's program, or compute it inside one)",
                detail=ast.unparse(node)))


def _direct_output_puts(tree: SourceTree, findings: list[Finding]) -> None:
    """Every ``<x>.outputs.put(...)`` of the engine file, module-level
    classes included, but for the delivery helpers' own."""
    allowed = set()
    for node in ast.walk(tree.tree(ENGINE)):
        if (isinstance(node, ast.FunctionDef)
                and node.name in DELIVERY_HELPERS):
            allowed.update(id(sub) for sub in ast.walk(node))
    for node in ast.walk(tree.tree(ENGINE)):
        if (isinstance(node, ast.Call) and id(node) not in allowed
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "put"
                and isinstance(node.func.value, ast.Attribute)
                and node.func.value.attr == "outputs"):
            findings.append(Finding(
                RULE, "direct-output-put", ENGINE, node.lineno,
                ENGINE_CLASS,
                "output put outside the delivery helper (route it through "
                "self._deliver so it cannot overtake a deferred frame)",
                detail=ast.unparse(node.func)))


def _trace_evt_impl(tree: SourceTree, findings: list[Finding]) -> None:
    path = "arks_tpu/obs/trace.py"
    if path not in tree.files:
        return
    mod = tree.tree(path)
    classes = {n.name: n for n in mod.body if isinstance(n, ast.ClassDef)}
    scopes = []
    tracer = classes.get("Tracer")
    if tracer is not None:
        evt = q.func_defs(tracer).get("evt")
        if evt is None:
            findings.append(Finding(
                RULE, "contract", path, tracer.lineno, "Tracer",
                "Tracer.evt disappeared — the step loop's only sanctioned "
                "tracing entry"))
        else:
            scopes.append(("Tracer.evt", evt))
    if "_Ring" in classes:
        scopes.append(("_Ring", classes["_Ring"]))
    for scope_name, scope in scopes:
        allowed = set()
        for node in ast.walk(scope):
            if isinstance(node, ast.ExceptHandler):
                # the sanctioned first-call-per-thread ring creation
                for sub in ast.walk(node):
                    allowed.add(id(sub))
        for node in ast.walk(scope):
            if id(node) in allowed:
                continue
            bad = None
            if isinstance(node, (ast.With, ast.AsyncWith)):
                bad = "with-block (lock?)"
            elif isinstance(node, ast.Attribute) and node.attr in (
                    "acquire", "Lock", "RLock", "sleep", "dumps", "loads",
                    "flush", "join"):
                bad = f".{node.attr}"
            elif isinstance(node, ast.Name) and node.id in ("json",
                                                            "pickle"):
                bad = node.id
            if bad:
                findings.append(Finding(
                    RULE, "trace-evt-impl", path, node.lineno, scope_name,
                    "lock/serialization on the event-record path",
                    detail=bad))


def _sketch_import(tree: SourceTree, findings: list[Finding]) -> None:
    path = "arks_tpu/prefix_sketch.py"
    if path not in tree.files:
        return
    for name, lineno in q.module_imports(tree.tree(path)):
        if name.startswith("jax") or name.startswith("arks_tpu.engine"):
            findings.append(Finding(
                RULE, "sketch-import", path, lineno, "<module>",
                "prefix_sketch must stay importable by the pure-I/O "
                "router process (no jax, no engine)", detail=name))


def check(tree: SourceTree) -> list[Finding]:
    findings: list[Finding] = []
    graph = CallGraph(tree)

    missing_roots = [r for r in ROOTS
                     if r[0] in tree.files and graph.find(*r) is None]
    for path, cls, name in missing_roots:
        findings.append(Finding(
            RULE, "contract", path, 1, f"{cls}.{name}" if cls else name,
            "hot-path root renamed/removed — re-anchor the rule's ROOTS"))

    if ENGINE in tree.files:
        engine_cls = q.class_def(tree.tree(ENGINE), ENGINE_CLASS)
        methods = q.func_defs(engine_cls) if engine_cls else {}
        for tail in EXPECTED_TAILS:
            if tail not in methods:
                findings.append(Finding(
                    RULE, "contract", ENGINE, 1,
                    f"{ENGINE_CLASS}.{tail}",
                    "sanctioned host-sync tail renamed/removed — the "
                    "issue-side guard is only meaningful while the sync "
                    "tails exist"))

        seq = _seq_step_roots(methods)
        for sec, names in seq.items():
            if not names:
                findings.append(Finding(
                    RULE, "contract", ENGINE, 1, ENGINE_CLASS,
                    f"no engine method opens a {sec!r} step section — "
                    "eager-device-call has lost its roots"))
        seq_roots = [graph.find(ENGINE, ENGINE_CLASS, n)
                     for n in sorted(set().union(*seq.values()))]
        for nid in sorted(graph.reachable([r for r in seq_roots if r])):
            _eager_device_calls(graph.nodes[nid], findings)

        for helper in DELIVERY_HELPERS:
            if helper not in methods:
                findings.append(Finding(
                    RULE, "contract", ENGINE, 1,
                    f"{ENGINE_CLASS}.{helper}",
                    "delivery helper renamed/removed — direct-output-put "
                    "has lost its one sanctioned door"))
        _direct_output_puts(tree, findings)

    roots = [nid for nid in (graph.find(*r) for r in ROOTS) if nid]
    reach = graph.reachable(
        roots, stop=lambda fn: bool(BOUNDARY_RE.match(fn.name)))
    for nid in sorted(reach):
        _function_findings(graph.nodes[nid], findings)

    _trace_evt_impl(tree, findings)
    _sketch_import(tree, findings)
    return findings
