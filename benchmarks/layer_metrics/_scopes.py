"""Device time by named scope (shared by the readers that take a share of
the busy time under an ``arks.<part>`` scope).

``jax.named_scope`` travels in each HLO op's metadata, and the profiler
keeps it as the stat ``tf_op`` of the op's EVENT METADATA on the device
plane (``jit(arks_mixed_seq)/while/body/arks.attn_layout/gather:``; seen
by hand in a v5e trace).  ``jax.profiler.ProfileData`` shows an event's own
stats only, not its metadata's, so this module walks the ``.xplane.pb``
wire format itself, and only as far as it must: per device plane the two
metadata maps; the lines with their tens of thousands of events are
skipped by their length (``trace_reduce.read_events`` has those already).

An op belongs to the INNERMOST ``arks.`` scope of its path: the Pallas call
inside ``arks.attn_layout`` is ``arks.attn_kernel``.  Times are self times:
an op that spans others on the line (the ``while`` over the layers) has
theirs taken out, as ``trace_reduce.self_times`` does.
"""

from __future__ import annotations

import re

DEVICE_PREFIX = "/device:TPU:"
SCOPE = re.compile(r"(?:^|/)(arks\.[A-Za-z0-9_]+)(?=/|:|$)")


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview, not a copy."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, wire, val


def _map_entry(buf):
    key = val = None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def op_paths(xplane_path: str) -> dict[str, str]:
    """Op name (as the ``XLA Ops`` events carry it) -> its ``tf_op`` path,
    from the first device plane that has any."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    for f, wire, plane in _fields(space):
        if f != 1 or wire != 2:
            continue
        name, events, stats = "", [], {}
        for pf, pw, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf == 4:                        # event_metadata map
                events.append(_map_entry(pv)[1])
            elif pf == 5:                        # stat_metadata map
                key, meta = _map_entry(pv)
                for sf, _, sv in _fields(meta):
                    if sf == 2:
                        stats[key] = bytes(sv).decode()
        if not name.startswith(DEVICE_PREFIX):
            continue
        tf_op = {k for k, v in stats.items() if v == "tf_op"}
        out = {}
        for meta in events:
            op, path = "", None
            for ef, _, ev in _fields(meta):
                if ef == 2:
                    op = bytes(ev).decode()
                elif ef == 5:                    # an XStat of the metadata
                    sid = sval = None
                    for sf, _, sv in _fields(ev):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:
                            sval = sv
                    if sid in tf_op and sval is not None:
                        path = bytes(sval).decode()
            if op and path:
                out[op] = path
        if out:
            return out
    return {}


def scope_of(path: str | None) -> str | None:
    found = SCOPE.findall(path or "")
    return found[-1] if found else None


def self_seconds(ops: list[tuple[str, float, float]], paths: dict[str, str]
                 ) -> dict[str | None, float]:
    """Seconds of self time per scope (None: ops under no ``arks.`` scope)."""
    out: dict[str | None, float] = {}
    stack: list[list] = []                       # [scope, end, self]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            scope, _, own = stack.pop()
            out[scope] = out.get(scope, 0.0) + max(own, 0.0)

    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([scope_of(paths.get(name)), s + d, d])
    close(float("inf"))
    return out


def by_scope(ctx) -> dict[str | None, float] | None:
    """Self seconds per scope over the traced slice, or None where there is
    no trace, or where its ops carry no ``arks.`` scope at all (a program
    from before the scopes: nothing to read)."""
    dev = ctx.get("device")
    if not dev or not dev.get("xplane") or not dev.get("ops"):
        return None
    if "scope_seconds" not in dev:
        try:
            paths = op_paths(dev["xplane"])
        except (OSError, ValueError, IndexError):
            paths = {}
        got = self_seconds(dev["ops"], paths)
        dev["scope_seconds"] = got if set(got) - {None} else None
    return dev["scope_seconds"]


def share(ctx, scope: str) -> float | None:
    """Self time under ``scope`` over the device's busy time, in percent."""
    got = by_scope(ctx)
    if not got or ctx["device"]["busy_s"] <= 0:
        return None
    return 100.0 * got.get(scope, 0.0) / ctx["device"]["busy_s"]
