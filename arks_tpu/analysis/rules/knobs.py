"""Rule ``knobs`` — the typed registry is the only ``ARKS_*`` reader.

257 raw env reads across the tree meant no single place knew the full
configuration surface, and defaults silently disagreed between call
sites and docs.  ``arks_tpu/utils/knobs.py`` is now the one sanctioned
reader; this rule enforces it statically:

- ``raw-env-read``      ``os.environ.get/[]/setdefault`` / ``os.getenv``
                        of an ``ARKS_*`` name outside the registry
                        module (f-string reads with an ``ARKS_`` prefix
                        included);
- ``raw-env-write``     ``os.environ[...] = `` of an ``ARKS_*`` name —
                        use ``knobs.push`` so writes stay
                        registry-checked;
- ``unregistered-knob`` a knobs accessor called with a literal name the
                        registry doesn't declare;
- ``dynamic-knob-name`` WARN: an accessor called with a computed name
                        (the registry can't vouch statically — keep the
                        candidate names registered);
- ``unused-knob``       WARN: a registered name that appears nowhere
                        else in the package (stale registry entry).

The registered set is extracted from the registry module's AST (the
``_k("NAME", ...)`` declarations) — the analyzer never imports the code
it checks.
"""

from __future__ import annotations

import ast

from arks_tpu.analysis import Finding, SourceTree
from arks_tpu.analysis import queries as q

RULE = "knobs"

REGISTRY_PATH = "arks_tpu/utils/knobs.py"
ACCESSORS = {"raw", "get_str", "get_int", "get_float", "get_bool",
             "get_list", "push", "is_registered"}
# Knobs read by out-of-package surfaces only (launch scripts) or exported
# into runtime containers: exempt from the unused-knob scan.
EXTERNAL_OK = {"ARKS_GANG_LEADER_ADDRESS", "ARKS_GANG_SIZE",
               "ARKS_GANG_WORKER_INDEX",
               # read through a computed name (workloads.
               # default_runtime_image's f-string) — the dynamic-knob-name
               # warn at that site is the audit trail
               "ARKS_RUNTIME_DEFAULT_VLLM_IMAGE",
               "ARKS_RUNTIME_DEFAULT_SGLANG_IMAGE",
               "ARKS_RUNTIME_DEFAULT_DYNAMO_IMAGE",
               "ARKS_RUNTIME_DEFAULT_JAX_IMAGE"}


def registered_names(tree: SourceTree) -> set[str]:
    if REGISTRY_PATH not in tree.files:
        return set()
    names: set[str] = set()
    for node in ast.walk(tree.tree(REGISTRY_PATH)):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "_k" and node.args
                and isinstance(node.args[0], ast.Constant)):
            names.add(node.args[0].value)
    return names


def _is_environ(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os")


def _arks_literal(node: ast.AST) -> str | None:
    """The ARKS_* name of a Constant or ARKS_-prefixed f-string arg."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and node.value.startswith("ARKS_"):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) \
                and str(head.value).startswith("ARKS_"):
            return ast.unparse(node)
    return None


def _module_consts(mod: ast.Module) -> dict[str, str]:
    """Module-level ``NAME = "literal"`` string bindings — an accessor
    called with such a name (slo's ``ENV_VAR`` style) resolves statically
    and doesn't trip the dynamic-name warn."""
    out: dict[str, str] = {}
    for stmt in mod.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and isinstance(stmt.value, ast.Constant) \
                and isinstance(stmt.value.value, str):
            out[stmt.targets[0].id] = stmt.value.value
    return out


def check(tree: SourceTree) -> list[Finding]:
    findings: list[Finding] = []
    registered = registered_names(tree)
    referenced: set[str] = set()

    for path in tree.paths():
        mod = tree.tree(path)
        consts = _module_consts(mod)
        if path == REGISTRY_PATH:
            # the registry's own declarations don't count as references
            # (else unused-knob could never fire)
            continue
        referenced |= {s for s in q.string_constants(mod)
                       if s.startswith("ARKS_")}
        for node in ast.walk(mod):
            # raw reads: os.environ.get / os.getenv / os.environ[...]
            if isinstance(node, ast.Call):
                f = node.func
                name = None
                if isinstance(f, ast.Attribute) and node.args:
                    if (_is_environ(f.value)
                            and f.attr in ("get", "setdefault",
                                           "pop")) \
                            or (f.attr == "getenv"
                                and isinstance(f.value, ast.Name)
                                and f.value.id == "os"):
                        name = _arks_literal(node.args[0])
                if name:
                    fn = q.enclosing_function(mod, node.lineno)
                    findings.append(Finding(
                        RULE, "raw-env-read", path, node.lineno, fn,
                        "raw ARKS_* env read — go through "
                        "arks_tpu.utils.knobs (the typed registry)",
                        detail=name))
                # accessor calls
                target = None
                if isinstance(f, ast.Attribute) \
                        and isinstance(f.value, ast.Name) \
                        and f.value.id == "knobs" and f.attr in ACCESSORS:
                    target = f.attr
                elif isinstance(f, ast.Name) and f.id in ACCESSORS \
                        and f.id not in ("raw", "push", "is_registered"):
                    # direct `from ... import get_int` style
                    target = f.id
                if target and node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Name) and arg.id in consts:
                        # named module constant → resolved statically
                        arg = ast.Constant(value=consts[arg.id])
                    if isinstance(arg, ast.Constant) \
                            and isinstance(arg.value, str):
                        if arg.value.startswith("ARKS_") \
                                and arg.value not in registered:
                            fn = q.enclosing_function(mod, node.lineno)
                            findings.append(Finding(
                                RULE, "unregistered-knob", path,
                                node.lineno, fn,
                                "knob not declared in the registry — add "
                                "it to arks_tpu/utils/knobs.py with type/"
                                "default/doc/subsystem",
                                detail=arg.value))
                    elif not isinstance(arg, ast.Constant):
                        fn = q.enclosing_function(mod, node.lineno)
                        findings.append(Finding(
                            RULE, "dynamic-knob-name", path, node.lineno,
                            fn,
                            "knob name computed at runtime — the registry "
                            "can't vouch statically; keep every candidate "
                            "registered", detail=ast.unparse(arg),
                            severity="warn"))
            elif isinstance(node, ast.Subscript) and _is_environ(
                    node.value):
                name = _arks_literal(node.slice)
                if name:
                    fn = q.enclosing_function(mod, node.lineno)
                    check_name = ("raw-env-read"
                                  if isinstance(node.ctx, ast.Load)
                                  else "raw-env-write")
                    verb = ("read" if isinstance(node.ctx, ast.Load)
                            else "write (use knobs.push)")
                    findings.append(Finding(
                        RULE, check_name, path, node.lineno, fn,
                        f"raw ARKS_* env {verb} — go through "
                        "arks_tpu.utils.knobs", detail=name))

    for name in sorted(registered - referenced - EXTERNAL_OK):
        findings.append(Finding(
            RULE, "unused-knob", REGISTRY_PATH, 1, "<registry>",
            "registered knob is referenced nowhere in the package — "
            "stale entry?", detail=name, severity="warn"))
    return findings
