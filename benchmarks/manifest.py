"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in files of its own, found by the name in the
manifest:

    benchmarks/configs/<config>/config.json   the public config.json keys
    benchmarks/configs/<config>/deploy.json   how the pod is started, which
                                              reference family it is held to
    benchmarks/references/<family>.py         the plain reference of a family
                                              of architectures
    benchmarks/traffic/<traffic>.json         the mix's parameters
    benchmarks/knees/<config>.<traffic>.json  the swept knee: callers or req/s
    benchmarks/layer_metrics/<metric>.json    what the metric reads
    benchmarks/layer_metrics/<metric>.py      ``read(ctx) -> float | None``

so a later PR adds a configuration, a mix, a cell, a metric or a reference
family by adding files and one entry each to ``BENCHMARK.json``, editing no
file here.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# What a reference family exports (README.md, "A reference family");
# ``kernel_shapes`` is optional.
FAMILY_CONTRACT = ("arch", "param_spec", "generate_weights", "forward")
# A key of ``reduced`` that counts what a chip holds of a layer, so that
# ``deploy.json`` has to state the share: routed experts, vocabulary rows.
_EXPERT_COUNT = re.compile(r"^(?!.*shared).*experts$")
_PROBE_IDS = 258        # ``correctness.probes`` draws token ids 2..257


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT, rehearsal: bool = False) -> dict:
    """``BENCHMARK.json``; for ``--rehearse`` the manifest of the same
    shape that holds the tiny CPU cells, ``benchmarks/rehearsal.json``."""
    if rehearsal:
        return _json(os.path.join(root, "benchmarks", "rehearsal.json"))
    return _json(os.path.join(root, "BENCHMARK.json"))


def config_dir(config: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmarks", "configs", config)


def traffic_path(traffic: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmarks", "traffic", traffic + ".json")


def knee_path(config: str, traffic: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmarks", "knees",
                        f"{config}.{traffic}.json")


def metric_paths(name: str, root: str = ROOT) -> tuple[str, str]:
    base = os.path.join(root, "benchmarks", "layer_metrics", name)
    return base + ".json", base + ".py"


def reference_path(family: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmarks", "references", family + ".py")


def _load(module: str, path: str):
    spec = importlib.util.spec_from_file_location(
        module + re.sub(r"\W", "_", os.path.basename(path)[:-3]), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def load_reference(family: str, root: str = ROOT):
    """The module of a reference family, loaded once a process (its
    compiled functions live in it)."""
    return _load("benchmarks.references._", reference_path(family, root))


def with_share(config: dict, deploy: dict) -> dict:
    """The configuration as its reference family reads it: ``config.json``,
    and under ``share`` what ``deploy.json`` states of the chip's share of
    a layer, where it states one."""
    return dict(config, share=deploy["share"]) if "share" in deploy \
        else config


def cell(manifest: dict, name: str, root: str = ROOT) -> dict:
    """Everything one run needs to know about a cell."""
    for w in manifest["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in manifest['workloads']]}")
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    cdir = config_dir(w["config"], root)
    mix = _json(traffic_path(w["traffic"], root))
    deploy = _json(os.path.join(cdir, "deploy.json"))
    out = {"name": name, "chips": w["chips"], "config_name": w["config"],
           "config_entry": entry, "config_dir": cdir,
           "config": with_share(_json(os.path.join(root, entry["file"])),
                                deploy),
           "deploy": deploy,
           "reference": load_reference(deploy["reference"], root),
           "traffic_name": w["traffic"],
           "mix_path": traffic_path(w["traffic"], root), "mix": mix,
           }
    knee = _json(knee_path(w["config"], w["traffic"], root))
    out["knee"] = knee
    out["load"] = knee["knee"] * mix["load_share"]
    out["end_to_end"] = [m for m in manifest["end_to_end"]
                         if name in m.get("workloads", [name])]
    e2e = {m["name"] for m in out["end_to_end"]}
    out["per_layer"] = [m for m in manifest["per_layer"]
                        if name in m.get("workloads", [name])
                        and m["moves"] in e2e]
    return out


def load_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of a per-layer metric."""
    return _load("benchmarks.layer_metrics._",
                 metric_paths(name, root)[1]).read


def _config_faults(c: dict, root: str) -> list[str]:
    """What is wrong with a configuration's reference family and share."""
    try:
        deploy = _json(os.path.join(config_dir(c["name"], root),
                                    "deploy.json"))
        config = _json(os.path.join(root, c["file"]))
    except (OSError, ValueError):
        return []                   # reported as a missing file by the caller
    bad = []
    family = deploy.get("reference")
    if not (isinstance(family, str) and _NAME.match(family)):
        bad.append(f"deploy.json names no reference family ({family!r})")
    elif not os.path.isfile(reference_path(family, root)):
        bad.append(f"reference family {family!r}: no "
                   f"benchmarks/references/{family}.py")
    else:
        mod = load_reference(family, root)
        lacks = [f for f in FAMILY_CONTRACT
                 if not callable(getattr(mod, f, None))]
        if lacks:
            bad.append(f"reference family {family!r} lacks {lacks}")
    held = [k for k in c["reduced"]
            if k == "vocab_size" or _EXPERT_COUNT.match(k)]
    share = deploy.get("share")
    if held and not isinstance(share, dict):
        bad.append(f"reduced lists {held}: deploy.json has to state the share")
    if not isinstance(share, dict):
        return bad
    chips, index = share.get("chips_per_layer"), share.get("index")
    if not (isinstance(chips, int) and isinstance(index, int)
            and 0 <= index < chips):
        bad.append(f"share: chips_per_layer {chips!r}, index {index!r}")
        return bad
    published = share.get("published") or {}
    if sorted(published) != sorted(held):
        bad.append(f"share: published counts {sorted(published)} beside the "
                   f"reduced counts {sorted(held)}")
        return bad
    for k in held:
        here, whole = config.get(k), published[k]
        if not (isinstance(here, int) and isinstance(whole, int)
                and here <= whole):
            bad.append(f"share: {k} {here!r} here of {whole!r} published")
        elif k == "vocab_size":
            if here * 8 < whole or here < _PROBE_IDS:
                bad.append(f"share: vocab_size {here} is under an eighth of "
                           f"{whole} or under the {_PROBE_IDS} ids the "
                           "probes draw from")
        elif here < 8 or here * chips < whole:
            bad.append(f"share: {k} {here} here of {whole} published over "
                       f"{chips} chips: under 8 routed experts held, or the "
                       "shares together do not hold every expert")
    return bad


def validate(manifest: dict, root: str = ROOT) -> list[str]:
    """What is wrong with the manifest and the files it names (the part of
    the builder's contract a test can hold without a chip)."""
    bad: list[str] = []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    # ``trace_in_run``: the driver measures with ``--trace 0`` and traces
    # with ``--trace 2`` (one run that measures first and traces after),
    # never ``--trace 1``.  Optional; absent reads as false.
    if set(manifest) - {"trace_in_run"} != want:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(want)} "
                   "(+ trace_in_run)")
        return bad
    if not isinstance(manifest.get("trace_in_run", False), bool):
        bad.append("trace_in_run must be true or false")
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        bad.append("run_seconds must be a whole number from 1 to 51")

    def names(kind: str, items: list[dict], keys: set[str],
              optional: set[str] = frozenset()) -> list[str]:
        seen = []
        for it in items:
            if not keys <= set(it) <= keys | optional:
                bad.append(f"{kind} {it.get('name')!r}: keys {sorted(it)}")
            n = it.get("name", "")
            if not _NAME.match(n):
                bad.append(f"{kind}: bad name {n!r}")
            if n in seen:
                bad.append(f"{kind}: duplicate name {n!r}")
            seen.append(n)
            for k in ("why", "layer") + (("source",) if kind == "config"
                                         else ()):
                v = it.get(k)
                if k in it and not (isinstance(v, str) and 1 <= len(v) <= 200
                                    and "\n" not in v and "\t" not in v):
                    bad.append(f"{kind} {n!r}: bad {k}")
        return seen

    configs = names("config", manifest["configs"],
                    {"name", "source", "file", "reduced", "why"})
    cells = names("workload", manifest["workloads"],
                  {"name", "config", "traffic", "chips", "why"})
    e2e = names("metric", manifest["end_to_end"],
                {"name", "unit", "better", "bound", "source"}, {"workloads"})
    names("metric", manifest["per_layer"],
          {"name", "unit", "better", "source", "layer", "moves"},
          {"workloads"})
    both = e2e + [m["name"] for m in manifest["per_layer"]]
    if len(set(both)) != len(both):
        bad.append("a metric name is used twice")
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for c in manifest["configs"]:
        path = os.path.join(root, c["file"])
        if not c["file"].startswith(tuple(p.rstrip("/") + "/"
                                          for p in manifest["paths"])):
            bad.append(f"config {c['name']!r}: file outside paths")
        if not os.path.isfile(path):
            bad.append(f"config {c['name']!r}: no file {c['file']}")
        if not os.path.isfile(os.path.join(config_dir(c["name"], root),
                                           "deploy.json")):
            bad.append(f"config {c['name']!r}: no deploy.json")
        if len(c["reduced"]) > 16 or any(
                not _NAME.match(k) or re.search(
                    r"(_dim|_rank|hidden_size|intermediate_size|head_dim|"
                    r"num_experts_per_tok)$", k) for k in c["reduced"]):
            bad.append(f"config {c['name']!r}: reduced names a width")
        bad += [f"config {c['name']!r}: {e}"
                for e in _config_faults(c, root)]
    used = set()
    pairs = set()
    four = 0
    for w in manifest["workloads"]:
        used.add(w["config"])
        if w["config"] not in configs:
            bad.append(f"workload {w['name']!r}: unknown config")
        if not _NAME.match(w["traffic"]):
            bad.append(f"workload {w['name']!r}: bad traffic name")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']!r}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']!r}: chips must be 1 or 4")
        four += w["chips"] == 4
        if not os.path.isfile(traffic_path(w["traffic"], root)):
            bad.append(f"workload {w['name']!r}: no traffic file")
        elif not os.path.isfile(knee_path(w["config"], w["traffic"], root)):
            bad.append(f"workload {w['name']!r}: no swept knee")
    if four > max(1, len(cells) // 4):
        bad.append("too many four-chip cells")
    if used != set(configs):
        bad.append(f"configs no cell uses: {sorted(set(configs) - used)}")

    def reported_in(metric: dict) -> set[str]:
        return set(metric.get("workloads", cells))

    by_name = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        n = m["name"]
        if not _UNIT.match(m.get("unit", "")):
            bad.append(f"metric {n!r}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {n!r}: better must be lower or higher")
        if m.get("source") not in _SOURCES:
            bad.append(f"metric {n!r}: bad source {m.get('source')!r}")
        if not reported_in(m) <= set(cells):
            bad.append(f"metric {n!r}: lists an unknown workload")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']!r}: an end-to-end metric is "
                       "measured by the benchmark itself")
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.1):
            bad.append(f"metric {m['name']!r}: bound {b!r}")
    for m in manifest["per_layer"]:
        n = m["name"]
        if m["moves"] not in by_name or m["moves"] == "setup_s":
            bad.append(f"metric {n!r}: moves {m['moves']!r}, which is not "
                       "an end-to-end metric")
        elif not reported_in(m) <= reported_in(by_name[m["moves"]]):
            bad.append(f"metric {n!r}: a listed cell does not report "
                       f"{m['moves']!r}")
        if re.search(r"roofline|mfu", n) and m["unit"] != "%":
            bad.append(f"metric {n!r}: a roofline share is in %")
        js, py = metric_paths(n, root)
        for p in (js, py):
            if not os.path.isfile(p):
                bad.append(f"metric {n!r}: no {os.path.relpath(p, root)}")
    for c in cells:
        got = [m["name"] for m in manifest["end_to_end"]
               if c in reported_in(m)]
        if "setup_s" not in got or len(got) < 2:
            bad.append(f"workload {c!r}: needs setup_s and one more "
                       "end-to-end metric")
        if not any(c in reported_in(m) for m in manifest["per_layer"]):
            bad.append(f"workload {c!r}: no per-layer metric")
    return bad
