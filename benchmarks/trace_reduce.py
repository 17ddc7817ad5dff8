"""From a profiler trace (``.xplane.pb``) to device metrics.

The one reduction every PR's traced run goes through; read with nothing
but ``jax.profiler.ProfileData``.

What a TPU trace holds (looked at by hand on a v5e, PR 23): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per
executed operation and whose line ``XLA Modules`` has one event per
executed program (``jit_<name>(<fingerprint>)``); and the host plane
``/host:CPU``, one line per thread, where ``jax.profiler.TraceAnnotation``
events such as the engine's ``arks_step[...]`` sit.  Every event has
``start_ns`` and ``duration_ns`` on one clock.

- busy: the union of the ``XLA Ops`` intervals of a chip, averaged over the
  chips that ran anything; the window is the span from the first to the
  last event of the slice (device or step annotation).
- a kernel's time: the sum of the durations of the ops whose name contains
  the kernel's ``name=``.  The breakdown lists SELF time: an op that spans
  others on the same line (a ``while`` over the layers) has theirs taken out.
- the trace's clock starts near 0 when the profiler starts collecting; the
  caller hands in ``time.monotonic()`` at that moment to lay the engine's
  own spans (which use that clock) on the trace.
- a program's step time: the durations of its ``XLA Modules`` events.
- an idle gap is attributed to what the host was doing at its midpoint:
  inside an ``arks_step`` annotation (and, where the engine's scheduler
  phase spans were handed in and could be laid on the same clock, which
  phase), between two steps, or with no request live.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_ANNOTATION = "arks_step"


def find_xplane(profile_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read_events(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "annotations": [...]}`` with every event as (name, start_s, dur_s)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    annotations = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    d[key] += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations += [
                    (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(STEP_ANNOTATION)]
    annotations.sort(key=lambda e: e[1])
    return {"devices": devices, "annotations": annotations}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(ops: list[tuple[str, float, float]]) -> float:
    return sum(e - s for s, e in union([(s, s + d) for _, s, d in ops]))


def sum_by_name(ops: list[tuple[str, float, float]], needle: str
                ) -> tuple[float, int]:
    hit = [d for n, _, d in ops if needle in n]
    return sum(hit), len(hit)


def short_name(op: str) -> str:
    """``%fusion.297 = bf16[448,4,7,128]{...} fusion(...)`` ->
    ``%fusion.297 = bf16[448,4,7,128]``: the trace names an op by its whole
    HLO line."""
    head = op.split("{", 1)[0].strip()
    return head[:96]


def self_times(ops: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds per op name with the time of ops nested inside it taken out
    (a ``while`` spans its whole body on the same line)."""
    out: dict[str, float] = {}
    stack: list[list] = []            # [name, end, self]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([short_name(name), s + d, d])
    close(float("inf"))
    return out


def step_programs(modules: list[tuple[str, float, float]]) -> dict:
    """The programs that are steps: those that ran three times or more in
    the slice and took 2 % or more of all program time.  Name -> durations."""
    by: dict[str, list[float]] = {}
    for name, _, d in modules:
        by.setdefault(name, []).append(d)
    total = sum(sum(v) for v in by.values()) or 1.0
    return {k: v for k, v in by.items()
            if len(v) >= 3 and sum(v) >= 0.02 * total}


def idle_gaps(ops: list[tuple[str, float, float]], t0: float, t1: float
              ) -> list[tuple[float, float]]:
    """The (start, end) stretches of [t0, t1] in which no op ran."""
    gaps, at = [], t0
    for s, e in union([(s, s + d) for _, s, d in ops]):
        if s > at:
            gaps.append((at, min(s, t1)))
        at = max(at, e)
    if at < t1:
        gaps.append((at, t1))
    return gaps


def attribute_gap(mid: float, annotations: list[tuple[str, float, float]],
                  phases: list[tuple[str, float, float]]) -> str:
    for name, s, d in annotations:
        if s <= mid <= s + d:
            if name == STEP_ANNOTATION:
                return "step with no request live"
            for pname, ps, pd in phases:
                if ps <= mid <= ps + pd:
                    return f"inside a step: {pname}"
            return "inside a step"
    return "between steps"


def reduce(events: dict, phase_spans: list[dict] | None = None,
           clock_offset_s: float | None = None) -> dict:
    """Busy, window, the breakdown, and the raw lists the per-layer readers
    sum over.  ``phase_spans`` are ``obs/trace.py``'s engine-scope spans on
    ``time.monotonic``; ``clock_offset_s`` is trace clock minus monotonic
    (None: the phases cannot be laid on the trace and are left out)."""
    devs = {k: v for k, v in events["devices"].items() if v["ops"]}
    ann = events["annotations"]
    if not devs:
        return {"busy_s": 0.0, "window_s": 0.0, "chips": 0, "ops": [],
                "modules": [], "annotations": ann,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    starts = [o[1] for d in devs.values() for o in d["ops"]]
    ends = [o[1] + o[2] for d in devs.values() for o in d["ops"]]
    if ann:
        # The slice is where steps were annotated: the profiler runs a
        # little longer than the engine's annotated steps on both sides.
        t0 = min(min(starts), ann[0][1])
        t1 = max(max(ends), ann[-1][1] + ann[-1][2])
    else:
        t0, t1 = min(starts), max(ends)
    busy = sum(busy_seconds(d["ops"]) for d in devs.values()) / len(devs)
    phases = []
    if phase_spans and clock_offset_s is not None:
        phases = [(p["name"], p["start"] + clock_offset_s,
                   (p["end"] or p["start"]) - p["start"])
                  for p in phase_spans if p["name"].startswith("phase.")]
    first = next(iter(devs.values()))
    by_op = self_times(first["ops"])
    by_gap: dict[str, float] = {}
    for s, e in idle_gaps(first["ops"], t0, t1):
        what = attribute_gap((s + e) / 2, ann, phases)
        by_gap[what] = by_gap.get(what, 0.0) + (e - s)
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": busy, "window_s": t1 - t0, "chips": len(devs),
            "ops": first["ops"], "modules": first["modules"],
            "annotations": ann,
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(by_gap)}}


def reduce_dir(profile_dir: str, t0_monotonic: float, t1_monotonic: float,
               phase_spans: list[dict] | None = None,
               clock_offset_s: float | None = None) -> dict:
    path = find_xplane(profile_dir)
    if path is None:
        raise RuntimeError(f"no .xplane.pb under {profile_dir}")
    out = reduce(read_events(path), phase_spans, clock_offset_s)
    out["slice_monotonic"] = (t0_monotonic, t1_monotonic)
    out["xplane"] = path
    return out
