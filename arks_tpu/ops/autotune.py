"""Persisted kernel autotune: (block_q, block_b, page-DMA depth) per shape.

The paged attention kernels' block sizes were hardcoded heuristics
(``block_b = 16 if int8 else 8``, ``block_q = min(qmax, 32)``) — right for
the one v5e shape they were measured on, wrong elsewhere.  This module
benchmarks the candidate grid per (kernel, model shape, kv dtype,
topology) signature, persists the winner in a JSON table, and serves it
back as a pure dict lookup at kernel trace time.

Modes (``ARKS_KERNEL_TUNE``):

- ``off``    — never look anything up; kernels use their built-in
               heuristics (byte-identical to the pre-autotune behavior).
- ``cached`` — (default) use a persisted table entry when one exists,
               heuristics otherwise.  NEVER sweeps: with no table on disk
               this is exactly ``off``, so fresh deployments stay
               byte-identical until an operator opts into a sweep.
- ``sweep``  — like ``cached``, but a missing entry triggers a benchmark
               sweep at warm-up (InferenceEngine.__init__) and persists
               the winner.

The split between :func:`lookup` (pure dict read, allowed at kernel trace
time and on the engine issue path) and :func:`ensure` (may sweep — warm-up
only) is structural: tests/test_hotpath_guard.py asserts the scheduler's
step loop can only ever reach the lookup side.

Block sizes are resolved at TRACE time (they are static kernel args), so
a table round-trip (persist -> load -> reuse) costs zero extra compiled
program variants: the same entry always resolves to the same statics.
"""

from __future__ import annotations

import json
import logging
import os
import threading

from arks_tpu.utils import knobs

log = logging.getLogger("arks.autotune")

_MODES = ("off", "cached", "sweep")

# In-memory table: {kernel: {signature: {param: value, ...}}}.  Loaded
# from disk at most once per path; guarded so concurrent engine threads
# cannot half-read a table mid-persist.
_lock = threading.Lock()
_table: dict | None = None
_table_path: str | None = None


def mode() -> str:
    m = (knobs.raw("ARKS_KERNEL_TUNE") or "cached").lower()
    if m not in _MODES:
        raise ValueError(
            f"ARKS_KERNEL_TUNE={m!r} (expected one of {_MODES})")
    return m


def cache_path() -> str:
    """JSON table location: ``ARKS_KERNEL_TUNE_CACHE`` wins; else the model
    dir (``ARKS_MODEL_DIR``) so the table ships next to the checkpoint it
    was tuned for; else a per-user cache dir."""
    p = knobs.get_str("ARKS_KERNEL_TUNE_CACHE")
    if p:
        return p
    base = knobs.get_str("ARKS_MODEL_DIR") or os.path.join(
        os.path.expanduser("~"), ".cache", "arks_tpu")
    return os.path.join(base, "kernel_tune.json")


def topology() -> str:
    """Backend x device-count signature — a table tuned on one topology
    must not silently steer another."""
    import jax
    return f"{jax.default_backend()}x{jax.device_count()}"


def mixed_signature(*, hkv: int, g: int, d: int, page: int, qmax: int,
                    kv: str) -> str:
    return f"hkv{hkv}-g{g}-d{d}-page{page}-q{qmax}-{kv}-{topology()}"


def decode_signature(*, b: int, hkv: int, g: int, d: int, page: int,
                     kv: str) -> str:
    return f"b{b}-hkv{hkv}-g{g}-d{d}-page{page}-{kv}-{topology()}"


def _load_locked() -> dict:
    """Load the table once per path (pure host file I/O — no device work,
    no blocking fetches; the hot-path guard covers this function)."""
    global _table, _table_path
    path = cache_path()
    if _table is not None and _table_path == path:
        return _table
    data: dict = {}
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            data = {}
    except (OSError, ValueError):
        data = {}
    _table, _table_path = data, path
    return data


def lookup(kernel: str, signature: str) -> dict | None:
    """Pure table read: the persisted winner for (kernel, signature), or
    None (mode=off, or no entry).  Safe at kernel trace time and on the
    engine issue path — this function can never sweep."""
    if mode() == "off":
        return None
    with _lock:
        entry = _load_locked().get(kernel, {}).get(signature)
    return dict(entry) if isinstance(entry, dict) else None


def record(kernel: str, signature: str, params: dict) -> None:
    """Persist one winner (atomic tmp+rename so a concurrent reader never
    sees a torn table)."""
    path = cache_path()
    with _lock:
        data = _load_locked()
        data.setdefault(kernel, {})[signature] = dict(params)
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError as e:  # read-only FS: keep the in-memory entry
            log.warning("autotune table not persisted to %s: %s", path, e)


def invalidate_cache() -> None:
    """Drop the in-memory table (tests / operators editing the JSON)."""
    global _table, _table_path
    with _lock:
        _table = _table_path = None


def sweep(kernel: str, signature: str, candidates: list[dict],
          bench_fn, repeats: int = 3) -> dict:
    """Time ``bench_fn(**candidate)`` for every candidate, persist and
    return the fastest.  ``bench_fn`` must block until the work is done
    (e.g. ``np.asarray`` the kernel output) — warm-up/bench context only,
    NEVER the serving step loop."""
    import time

    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            bench_fn(**cand)  # compile / warm outside the timed window
            t0 = time.perf_counter()
            for _ in range(repeats):
                bench_fn(**cand)
            t = (time.perf_counter() - t0) / repeats
        except Exception as e:  # an infeasible candidate is not fatal
            # ... but never silent: a candidate the chip's compiler refuses
            # must not look like one that merely lost the timing.
            first = (str(e).strip().splitlines() or [""])[0]
            log.warning("autotune %s %s: candidate %s refused: %s: %s",
                        kernel, signature, cand, type(e).__name__, first)
            log.debug("autotune candidate %s traceback", cand, exc_info=True)
            continue
        log.info("autotune %s %s %s: %.3f ms", kernel, signature, cand,
                 t * 1e3)
        if t < best_t:
            best, best_t = cand, t
    if best is None:
        raise RuntimeError(
            f"autotune sweep for {kernel}/{signature}: every candidate "
            "failed")
    record(kernel, signature, best)
    return dict(best)


def ensure(kernel: str, signature: str, candidates: list[dict],
           bench_fn, repeats: int = 3) -> dict | None:
    """Mode-aware warm-up entry: cached entry if present; in ``sweep``
    mode a miss runs the sweep; otherwise None (heuristics)."""
    got = lookup(kernel, signature)
    if got is not None or mode() != "sweep":
        return got
    return sweep(kernel, signature, candidates, bench_fn, repeats=repeats)
