#!/usr/bin/env python3
"""chip_smoke.py — the serving pod, end to end, on the chip.

    python3 chip_smoke.py          # one chip
    python3 chip_smoke.py --tp4    # four chips: the tensor-parallel path only

The quickest proof that the program still starts on a TPU.  It builds the
pod the workload controller launches (``python -m arks_tpu.server``)
through that entry point's own functions, at the full width and depth of
qwen2.5-7b with seeded random int8 weights, answers real HTTP, and checks
what comes back.  One process holds the chip: the engine lives in this
process and the HTTP clients are threads.

The phases are functions that take the model and the shapes, so that
tests/test_chip_smoke.py drives the same code with the ``tiny`` model on
the CPU.  The command's first act, outside them, is the platform check:
without a TPU it builds nothing and exits 1.

Every line of standard output is one JSON object.  The last is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
or, after the first failed check or exception in any phase,
``{"ok": false, ...}`` with exit code 1.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import logging
import random
import string
import sys
import threading
import time
import traceback

MODEL = "qwen2.5-7b"
# The shape on record and the one the first benchmark cells will use.
NUM_SLOTS = 192
MAX_MODEL_LEN = 1024
SEED = 0
# What a TPU must resolve the engine to (engine_config_info labels).
TPU_LABELS = {
    "kv_layout": "paged", "decode_impl": "pallas", "mixed_step": "true",
    "mixed_grid": "ragged", "kv_dtype": "int8", "pipeline_depth": "2",
    "overlap": "true",
}
# Kernel-vs-oracle bound on the attention output, relative to the oracle's
# largest magnitude.  Both sides round probabilities to bf16 (2^-8) before
# the PV matmul, in a different order; 2^-6 leaves two bits of headroom and
# is far below what a wrong page, mask or scale produces.
PARITY_REL_BOUND = 2.0 ** -6
# tp=4 against one device, both bounds fixed before the run that met them.
#
# In f32 (the same stored weights widened inside the program, matmuls at
# the highest precision) the two differ only by f32 rounding, 2^-24 a step
# over some hundreds of steps and 18944-long sums: of the order of 1e-5.
# 2^-10 is a hundred times that and a tenth of what ONE bf16 rounding per
# layer would add (2^-9 * sqrt(28)), so it holds the sharded program to the
# same function: a wrong shard, a missing psum or a mis-mapped head fails
# it.
TP_F32_REL_BOUND = 2.0 ** -10
# In bf16 they are two compilations that place their roundings
# differently.  A rounding moves a value by 2^-9 of itself (rms); about ten
# of them sit on the residual path of a layer, in each program, so the
# streams part by about 2^-9 * sqrt(2 * 10 * layers), 4.6% at 28 layers.
# The bound is twice that; it is the stated bf16 tolerance, not what says
# the shards are right (the f32 bound does).
TP_BF16_ROUNDINGS_PER_LAYER = 10


def tp_bf16_rel_bound(layers: int) -> float:
    return 2 * 2.0 ** -9 * (2 * TP_BF16_ROUNDINGS_PER_LAYER * layers) ** 0.5


# Bytes in use per device after load.  A whole copy of the weights left on
# device 0 would make it about 2.7x its neighbours at qwen2.5-7b / tp=4.
TP_BYTES_FACTOR = 1.5
# No vocabulary holds these ids, so they stop nothing; a stop set of more
# than sampler.STOP_IDS_MAX (32) ids keeps the engine on its sequential
# step program while the request is live (docs/application-usage.md,
# "Pipelined decoding").
SEQUENTIAL_STOP_IDS = [2**30 + i for i in range(33)]


class SmokeFailure(Exception):
    """A check failed."""


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# Instruments
# ---------------------------------------------------------------------------


class CompileMeter:
    """Counts XLA compilations and persistent-cache hits/misses through
    jax.monitoring (listeners run on whichever thread compiles)."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self._lock = threading.Lock()
        self._n = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                   "cache_misses": 0}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._n["compiles"] += 1
                self._n["compile_s"] += secs

    def _on_event(self, event: str, **_) -> None:
        key = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"
               }.get(event)
        if key:
            with self._lock:
                self._n[key] += 1

    def since(self, mark: dict | None = None) -> dict:
        with self._lock:
            now = dict(self._n)
        if mark:
            now = {k: now[k] - mark[k] for k in now}
        now["compile_s"] = round(now["compile_s"], 2)
        return now

    def mark(self) -> dict:
        with self._lock:
            return dict(self._n)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_stats() -> list[dict]:
    """bytes in use / peak / limit per local device (empty where the
    backend reports none, as the CPU does)."""
    import jax
    out = []
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        if ms:
            out.append({"device": d.id,
                        "bytes_in_use": ms.get("bytes_in_use"),
                        "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                        "bytes_limit": ms.get("bytes_limit")})
    return out


# ---------------------------------------------------------------------------
# Phase: kernels against the XLA oracle, on the device
# ---------------------------------------------------------------------------


def _parity_lanes(*, hkv: int, d: int, page: int, max_pages: int,
                  chunk: int, decode_lanes: int, kv: str, layers: int,
                  seed: int, chunk_lanes: int = 1) -> dict:
    """A seeded pool and one mixed batch over it: decode lanes at spread
    lengths, the prefill chunk's rows behind a cached page (one lane, or
    shared evenly among ``chunk_lanes`` as a burst's admission shares the
    budget), one idle lane.  Every lane owns its pages; page 0 stays
    unmapped."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lanes = decode_lanes + chunk_lanes + 1
    n_pages = lanes * max_pages + 1
    cover = max_pages * page
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    rows = page // 2 if kv == "int4" else page
    if kv == "bf16":
        kp = jax.random.normal(keys[0], (layers, n_pages, hkv, page, d),
                               jnp.bfloat16)
        vp = jax.random.normal(keys[1], kp.shape, jnp.bfloat16)
        ks = vs = None
    else:
        shape = (layers, n_pages, hkv, rows, d)
        kp = jax.random.randint(keys[0], shape, -127, 128, jnp.int8)
        vp = jax.random.randint(keys[1], shape, -127, 128, jnp.int8)
        ks = jax.random.uniform(keys[2], (layers, n_pages, hkv, page),
                                jnp.float32, 0.01, 0.03)
        vs = jax.random.uniform(keys[3], ks.shape, jnp.float32, 0.01, 0.03)
    tables = 1 + np.arange(lanes * max_pages, dtype=np.int32).reshape(
        lanes, max_pages)
    # Decode lanes: one token each, at positions spread over the window
    # (first and last positions of the table included).
    dec_pos = np.linspace(0, cover - 1, decode_lanes).astype(np.int32)
    check(page + chunk <= cover, "parity shape: chunk past the table")
    q_start = np.zeros((lanes,), np.int32)
    q_len = np.zeros((lanes,), np.int32)
    pos0 = np.zeros((lanes,), np.int32)
    q_start[:decode_lanes] = np.arange(decode_lanes)
    q_len[:decode_lanes] = 1
    pos0[:decode_lanes] = dec_pos
    # The chunk sits behind one page; shared, lane i's part starts i rows
    # further on, so the parts end on and off the kernel's block edges.
    at = decode_lanes
    for i in range(chunk_lanes):
        rows = chunk // chunk_lanes + (i < chunk % chunk_lanes)
        lane = decode_lanes + i
        q_start[lane], q_len[lane], pos0[lane] = at, rows, page + i
        at += rows
    return dict(lanes=lanes, keys=keys[4:], kp=kp, vp=vp, ks=ks, vs=vs,
                tables=tables, q_start=q_start, q_len=q_len, pos0=pos0)


def kernel_parity(*, kv: str, hkv: int, g: int, d: int, page: int,
                  max_pages: int, chunk: int, decode_lanes: int,
                  chunk_lanes: int = 1,
                  layers: int = 2, seed: int = SEED, mesh=None,
                  rel_bound: float = PARITY_REL_BOUND) -> dict:
    """``paged_kv_update[_quant]`` + ``paged_mixed_attention_flat`` against
    ``paged_update_xla`` + ``paged_gather_kv`` + the XLA decode attention,
    through the one dispatcher the model calls: decode lanes, the prefill
    chunk (on one lane or shared among ``chunk_lanes``) and one idle lane
    in a single call.  The written pool must match
    bit for bit (both sides quantize with the same XLA code; only the
    writer differs); the attention output within ``rel_bound`` of the
    oracle's largest value.

    With ``mesh`` both sides run under it with the KV heads sharded over
    its model axis, as a tensor-parallel engine places them: the kernels
    inside ``shard_map`` against the XLA path under the same sharding."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from arks_tpu.ops.attention import paged_mixed_update_and_attend

    c = _parity_lanes(hkv=hkv, d=d, page=page, max_pages=max_pages,
                      chunk=chunk, decode_lanes=decode_lanes, kv=kv,
                      layers=layers, seed=seed, chunk_lanes=chunk_lanes)
    lanes = c["lanes"]
    t_flat = lanes + chunk                # the engine's slots + budget shape
    q = jax.random.normal(c["keys"][0], (t_flat, hkv * g, d), jnp.bfloat16)
    k_new = jax.random.normal(c["keys"][1], (t_flat, hkv, d), jnp.bfloat16)
    v_new = jax.random.normal(c["keys"][2], (t_flat, hkv, d), jnp.bfloat16)
    token_slot = np.full((t_flat,), -1, np.int32)
    token_pos = np.zeros((t_flat,), np.int32)
    for lane in range(lanes):
        at = slice(c["q_start"][lane], c["q_start"][lane] + c["q_len"][lane])
        token_slot[at] = lane
        token_pos[at] = c["pos0"][lane] + np.arange(c["q_len"][lane])
    pools = [c["kp"], c["vp"], c["ks"], c["vs"]]
    if mesh is not None:
        def place(x, *spec):
            return None if x is None else jax.device_put(
                x, NamedSharding(mesh, P(*spec)))
        q, k_new, v_new = (place(x, None, "model", None)
                           for x in (q, k_new, v_new))
        pools = [place(pools[0], None, None, "model", None, None),
                 place(pools[1], None, None, "model", None, None),
                 place(pools[2], None, None, "model", None),
                 place(pools[3], None, None, "model", None)]

    def run(impl: str):
        fn = jax.jit(functools.partial(
            paged_mixed_update_and_attend, impl=impl, mesh=mesh,
            kv_sharded=mesh is not None))
        out = fn(q, k_new, v_new, pools[0], pools[1],
                 jnp.asarray(c["tables"]), jnp.asarray(token_slot),
                 jnp.asarray(token_pos), jnp.asarray(c["q_start"]),
                 jnp.asarray(c["q_len"]), jnp.asarray(c["pos0"]),
                 jnp.asarray(1, jnp.int32), k_scale=pools[2],
                 v_scale=pools[3])
        return [None if x is None else np.asarray(x) for x in out]

    t0 = time.monotonic()
    got = run("pallas")
    ref = run("xla")
    valid = token_slot >= 0
    out_g = got[0][valid].astype(np.float32)
    out_r = ref[0][valid].astype(np.float32)
    check(np.isfinite(out_g).all(), f"parity[{kv}]: kernel output not finite")
    max_diff = float(np.max(np.abs(out_g - out_r)))
    ref_max = float(np.max(np.abs(out_r)))
    pool_equal = all(
        (a is None and b is None) or np.array_equal(a, b)
        for a, b in zip(got[1:], ref[1:]))
    res = {"kv": kv, "hkv": hkv, "g": g, "d": d, "page": page,
           "lanes": lanes, "chunk": chunk, "chunk_lanes": chunk_lanes,
           "mesh": None if mesh is None else dict(mesh.shape),
           "max_abs_diff": max_diff,
           "ref_max_abs": ref_max, "rel_diff": max_diff / ref_max,
           "rel_bound": rel_bound, "pool_bit_equal": pool_equal,
           "seconds": round(time.monotonic() - t0, 2)}
    emit(phase="kernel_parity", **res)
    check(pool_equal, f"parity[{kv}]: updated pool differs from the oracle's")
    check(max_diff <= rel_bound * ref_max,
          f"parity[{kv}]: max|kernel - oracle| = {max_diff:.5f} exceeds "
          f"{rel_bound:.5f} x {ref_max:.3f}")
    return res


def latent_parity(*, heads: int, row: int, value: int, page: int,
                  max_pages: int, chunk: int, decode_lanes: int,
                  chunk_lanes: int = 1, layers: int = 2, seed: int = SEED,
                  rel_bound: float = PARITY_REL_BOUND) -> dict:
    """``kernel_parity`` for a LATENT pool: one ``row``-wide row a token
    (stored lane-padded, as the engine stores it) that is key and value of
    all ``heads`` heads, through ``paged_latent_update_and_attend``: the
    row write and ``paged_latent_attention_ragged`` (Hkv = 1, the heads as
    the query group, values the first ``value`` lanes of the key tile)
    against the XLA gather.  The written pool bit for bit, the output
    within ``rel_bound`` of the oracle's largest value."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from arks_tpu.ops.attention import paged_latent_update_and_attend

    stored = -(-row // 128) * 128
    c = _parity_lanes(hkv=1, d=stored, page=page, max_pages=max_pages,
                      chunk=chunk, decode_lanes=decode_lanes, kv="bf16",
                      layers=layers, seed=seed, chunk_lanes=chunk_lanes)
    lanes = c["lanes"]
    t_flat = lanes + chunk
    live = jnp.arange(stored) < row             # padded lanes hold zeros
    pool = jnp.where(live, c["kp"], 0)
    q = jax.random.normal(c["keys"][0], (t_flat, heads, row), jnp.bfloat16)
    new = jax.random.normal(c["keys"][1], (t_flat, row), jnp.bfloat16)
    token_slot = np.full((t_flat,), -1, np.int32)
    token_pos = np.zeros((t_flat,), np.int32)
    for lane in range(lanes):
        at = slice(c["q_start"][lane], c["q_start"][lane] + c["q_len"][lane])
        token_slot[at] = lane
        token_pos[at] = c["pos0"][lane] + np.arange(c["q_len"][lane])

    def run(impl: str):
        fn = jax.jit(functools.partial(
            paged_latent_update_and_attend, dv=value, scale=row ** -0.5,
            impl=impl))
        out = fn(q, new, pool, jnp.asarray(c["tables"]),
                 jnp.asarray(token_slot), jnp.asarray(token_pos),
                 jnp.asarray(c["q_start"]), jnp.asarray(c["q_len"]),
                 jnp.asarray(c["pos0"]), jnp.asarray(1, jnp.int32))
        return [np.asarray(x) for x in out]

    t0 = time.monotonic()
    got, ref = run("pallas"), run("xla")
    valid = token_slot >= 0
    out_g = got[0][valid].astype(np.float32)
    out_r = ref[0][valid].astype(np.float32)
    check(np.isfinite(out_g).all(), "latent parity: kernel output not finite")
    max_diff = float(np.max(np.abs(out_g - out_r)))
    ref_max = float(np.max(np.abs(out_r)))
    pool_equal = bool(np.array_equal(got[1], ref[1]))
    res = {"heads": heads, "row": row, "stored": stored, "value": value,
           "page": page, "lanes": lanes, "chunk": chunk,
           "chunk_lanes": chunk_lanes, "max_abs_diff": max_diff,
           "ref_max_abs": ref_max, "rel_diff": max_diff / ref_max,
           "rel_bound": rel_bound, "pool_bit_equal": pool_equal,
           "seconds": round(time.monotonic() - t0, 2)}
    emit(phase="latent_parity", **res)
    check(pool_equal, "latent parity: updated pool differs from the oracle's")
    check(max_diff <= rel_bound * ref_max,
          f"latent parity: max|kernel - oracle| = {max_diff:.5f} exceeds "
          f"{rel_bound:.5f} x {ref_max:.3f}")
    return res


def head_group_parity(*, kv: str, hkv: int, g: int, d: int, page: int,
                      max_pages: int, chunk: int, decode_lanes: int,
                      layers: int = 2, seed: int = SEED,
                      rel_bound: float = PARITY_REL_BOUND) -> dict:
    """The mixed kernel streaming its KV heads in groups (the variant a
    kernel tune table can select; no table exists on a fresh machine, so
    serving never launches it there) against the ungrouped launch on the
    same batch.  Grouping only reorders the DMAs: on a quantised pool each
    group must still pick its own rows of the page's scale stripe."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arks_tpu.ops.paged_attention import paged_mixed_attention

    c = _parity_lanes(hkv=hkv, d=d, page=page, max_pages=max_pages,
                      chunk=chunk, decode_lanes=decode_lanes, kv=kv,
                      layers=layers, seed=seed)
    qs = jax.random.normal(c["keys"][0], (c["lanes"], hkv, g, chunk + 1, d),
                           jnp.bfloat16)
    live = (np.arange(chunk + 1)[None] < c["q_len"][:, None])

    def run(head_group: int):
        out = paged_mixed_attention(
            qs, c["kp"], c["vp"], jnp.asarray(c["tables"]),
            jnp.asarray(c["pos0"]), jnp.asarray(c["q_len"]), 1,
            k_scale=c["ks"], v_scale=c["vs"], head_group=head_group,
            interpret=jax.default_backend() != "tpu")
        return np.asarray(out, np.float32).transpose(0, 3, 1, 2, 4)[live]

    t0 = time.monotonic()
    ref = run(hkv)
    ref_max = float(np.max(np.abs(ref)))
    diffs = {}
    for head_group in (x for x in (1, 2) if x < hkv and hkv % x == 0):
        got = run(head_group)
        check(np.isfinite(got).all(),
              f"head_group={head_group}: kernel output not finite")
        diffs[str(head_group)] = float(np.max(np.abs(got - ref)))
    res = {"kv": kv, "hkv": hkv, "max_abs_diff_by_head_group": diffs,
           "ref_max_abs": ref_max, "rel_bound": rel_bound,
           "bit_equal": not any(diffs.values()),
           "seconds": round(time.monotonic() - t0, 2)}
    emit(phase="head_group_parity", **res)
    check(diffs and max(diffs.values()) <= rel_bound * ref_max,
          f"grouped launches differ from the ungrouped one by {diffs} "
          f"(> {rel_bound:.5f} x {ref_max:.3f})")
    return res


# ---------------------------------------------------------------------------
# Phase: the pod
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Pod:
    engine: object
    server: object

    @property
    def port(self) -> int:
        return self.server.port

    @property
    def labels(self) -> dict:
        """The resolved configuration (engine_config_info's labels)."""
        return self.engine.resolved_config

    def close(self) -> None:
        self.server.stop()
        self.engine.stop()


def server_argv(model: str, *, num_slots: int, max_model_len: int,
                weight_dtype: str, tp: int,
                extra: tuple[str, ...] = ()) -> list[str]:
    """The serving pod's command line.  --kv-cache-dtype and --kv-layout
    stay at ``auto``: what they resolve to is one of the checks.  The
    tensor-parallel size is always given: left out, the server takes every
    visible device."""
    return ["--model", model, "--weight-dtype", weight_dtype,
            "--num-slots", str(num_slots),
            "--max-model-len", str(max_model_len),
            "--tensor-parallel-size", str(tp),
            "--seed", str(SEED), "--host", "127.0.0.1", "--port", "0",
            *extra]


def build_pod(argv: list[str], expect_labels: dict, meter: CompileMeter) -> Pod:
    """Build the engine and the HTTP server through the functions
    ``python -m arks_tpu.server`` runs, start both, and hold the resolved
    configuration to ``expect_labels``."""
    from arks_tpu.server.__main__ import build_engine, build_server, parse_args

    mark = meter.mark()
    t0 = time.monotonic()
    args = parse_args(argv)
    engine = build_engine(args)
    t_engine = time.monotonic() - t0
    server = build_server(args, engine)
    server.start(background=True)
    pod = Pod(engine, server)
    labels, cfg = pod.labels, engine.cfg
    emit(phase="build", model=cfg.name, layers=cfg.num_layers,
         hidden=cfg.hidden_size, heads=cfg.num_heads,
         kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
         num_slots=engine.ecfg.num_slots,
         max_model_len=engine.ecfg.max_cache_len,
         engine_build_s=round(t_engine, 2), labels=labels,
         memory=memory_stats(), **meter.since(mark))
    try:
        bad = {k: (labels.get(k), v) for k, v in expect_labels.items()
               if labels.get(k) != v}
        check(not bad, f"engine_config_info (got, want): {bad}")
    except BaseException:
        pod.close()
        raise
    return pod


# One request per connection: the server must not be left waiting on a
# kept-alive socket that this client then drops.
_HEADERS = {"Content-Type": "application/json", "Connection": "close"}


def _post(port: int, path: str, body: dict, timeout: float) -> tuple:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body), _HEADERS)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _get(port: int, path: str, timeout: float = 60.0) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path, headers=_HEADERS)
        resp = conn.getresponse()
        data = resp.read().decode()
        check(resp.status == 200, f"GET {path} -> {resp.status}")
        return data
    finally:
        conn.close()


def stream_completion(port: int, body: dict, timeout: float) -> dict:
    """One SSE /v1/completions request.  Returns text, finish_reason, usage
    and the number of content frames."""
    body = dict(body, stream=True, stream_options={"include_usage": True})
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body), _HEADERS)
        resp = conn.getresponse()
        if resp.status != 200:
            return {"status": resp.status, "error": resp.read().decode()[:300]}
        text, finish, usage, frames, done = "", None, None, 0, False
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                done = True
                break
            obj = json.loads(data)
            if "error" in obj:
                return {"status": 200, "error": json.dumps(obj["error"])[:300]}
            for ch in obj.get("choices") or ():
                if ch.get("text"):
                    text += ch["text"]
                    frames += 1
                finish = ch.get("finish_reason") or finish
            usage = obj.get("usage") or usage
        return {"status": 200, "text": text, "finish_reason": finish,
                "usage": usage, "frames": frames, "done": done}
    finally:
        conn.close()


def _check_answer(name: str, res: dict, prompt_tokens: int,
                  max_tokens: int) -> None:
    check(res.get("status") == 200 and "error" not in res,
          f"{name}: HTTP {res.get('status')} {res.get('error', '')}")
    check(res["finish_reason"] == "length",
          f"{name}: finish_reason {res['finish_reason']!r}, want 'length'")
    usage = res.get("usage") or {}
    check(usage.get("prompt_tokens") == prompt_tokens
          and usage.get("completion_tokens") == max_tokens,
          f"{name}: usage {usage}, want prompt={prompt_tokens} "
          f"completion={max_tokens}")


def parse_metrics(text: str) -> dict[str, list[tuple[dict, float]]]:
    """Prometheus text -> {name: [(labels, value), ...]}."""
    out: dict[str, list] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        if rest:
            for part in rest.rstrip("}").split('",'):
                k, _, v = part.partition('="')
                labels[k.strip()] = v.rstrip('"')
        out.setdefault(name, []).append((labels, float(val)))
    return out


def metric_sum(metrics: dict, name: str) -> float:
    return sum(v for _, v in metrics.get(name, ()))


@dataclasses.dataclass
class Traffic:
    """The request mix.  Lengths are tokens: the byte tokenizer makes one
    token of every ASCII character."""
    prompt_lens: tuple[int, ...]      # cycled over the concurrent streams
    max_tokens: int
    streams: int                      # concurrent SSE completions per wave
    waves: int
    prefix_len: int                   # shared prefix of the prefix pair
    timeout_s: float = 600.0


def _prompt(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(string.ascii_letters + " ", k=n))


def _pipelined_issues(pod: Pod) -> float:
    return metric_sum(parse_metrics(_get(pod.port, "/metrics")),
                      "pipeline_depth_occupancy_count")


def warm_up(pod: Pod, traffic: Traffic, meter: CompileMeter) -> None:
    """First requests: every program of the default path compiles here (the
    engine has no ahead-of-time warm-up; programs build at first dispatch,
    the pipelined pair off-thread).  At depth >= 1 the same greedy request
    is answered once by the sequential step program and once by the
    pipelined one — which program answered is read from the dispatch
    counter, not assumed — and the two streams must be identical."""
    mark = meter.mark()
    t0 = time.monotonic()
    rng = random.Random(SEED)
    plen = traffic.prompt_lens[0]
    body = {"prompt": _prompt(rng, plen), "temperature": 0,
            "max_tokens": traffic.max_tokens, "ignore_eos": True}
    depth = int(pod.labels["pipeline_depth"])
    first = stream_completion(
        pod.port, dict(body, stop_token_ids=SEQUENTIAL_STOP_IDS),
        traffic.timeout_s)
    _check_answer("warm-up (sequential)", first, plen, traffic.max_tokens)
    check(_pipelined_issues(pod) == 0,
          "warm-up: a request with an oversized stop set was pipelined")
    # A failed off-thread build of the pipelined programs leaves the engine
    # on the sequential path with only a warning in the log.
    state = pod.engine._pipe_warm_wait(traffic.timeout_s)
    check(state == ("ready" if depth else None),
          f"pipelined decode programs at depth {depth}: warm state {state!r}")
    second = stream_completion(pod.port, body, traffic.timeout_s)
    _check_answer("warm-up (pipelined)", second, plen, traffic.max_tokens)
    issues = _pipelined_issues(pod)
    check((issues > 0) == (depth > 0),
          f"warm-up at depth {depth}: {issues} pipelined dispatches")
    emit(phase="warmup", seconds=round(time.monotonic() - t0, 2),
         pipe_programs=state, pipelined_dispatches=issues,
         memory=memory_stats(), **meter.since(mark))
    check(second["text"] == first["text"],
          "warm-up: the same greedy request was answered differently the "
          "second time (sequential, then pipelined at depth >= 1)")


def drive_traffic(pod: Pod, traffic: Traffic, meter: CompileMeter) -> dict:
    """The checked traffic: concurrent SSE completions of mixed prompt
    lengths (greedy and seeded-sampled) with a chat completion among them,
    the same greedy request twice, and a prefix pair sent in sequence."""
    mark = meter.mark()
    t0 = time.monotonic()
    port, rng = pod.port, random.Random(SEED + 1)
    n_req = 0

    # Same greedy request twice -> identical streams.
    plen = traffic.prompt_lens[len(traffic.prompt_lens) // 2]
    body = {"prompt": _prompt(rng, plen), "temperature": 0,
            "max_tokens": traffic.max_tokens, "ignore_eos": True}
    twice = [stream_completion(port, body, traffic.timeout_s) for _ in (0, 1)]
    for i, res in enumerate(twice):
        _check_answer(f"greedy repeat {i}", res, plen, traffic.max_tokens)
    check(twice[0]["text"] == twice[1]["text"],
          "the same greedy request answered differently the second time")
    n_req += 2

    # Waves of concurrent streams, one chat completion inside the first.
    results: dict[str, dict] = {}

    def one_stream(name: str, body: dict) -> None:
        results[name] = stream_completion(port, body, traffic.timeout_s)

    def one_chat(name: str, body: dict) -> None:
        status, data = _post(port, "/v1/chat/completions", body,
                             traffic.timeout_s)
        results[name] = {"status": status, "body": data}

    chat_tokens = traffic.max_tokens
    for wave in range(traffic.waves):
        results.clear()
        threads, want = [], {}
        for i in range(traffic.streams):
            n = traffic.prompt_lens[i % len(traffic.prompt_lens)]
            body = {"prompt": _prompt(rng, n), "ignore_eos": True,
                    "max_tokens": traffic.max_tokens}
            if i % 2:
                body.update(temperature=0.8, top_p=0.95, seed=1000 + i)
            else:
                body.update(temperature=0)
            name = f"wave{wave}/stream{i}"
            want[name] = n
            threads.append(threading.Thread(target=one_stream,
                                            args=(name, body)))
        if wave == 0:
            chat = {"messages": [{"role": "user", "content": _prompt(rng, 48)}],
                    "max_tokens": chat_tokens, "temperature": 0,
                    "ignore_eos": True}
            threads.append(threading.Thread(target=one_chat,
                                            args=("wave0/chat", chat)))
        for t in threads:
            t.start()
        for t in threads:
            t.join(traffic.timeout_s)
            check(not t.is_alive(), f"wave {wave}: a client never returned")
        for name, n in want.items():
            check(name in results, f"{name}: no result")
            _check_answer(name, results[name], n, traffic.max_tokens)
        if wave == 0:
            res = results.get("wave0/chat") or {}
            check(res.get("status") == 200,
                  f"chat: HTTP {res.get('status')} {res.get('body', b'')[:300]}")
            obj = json.loads(res["body"])
            choice = obj["choices"][0]
            check(choice["finish_reason"] == "length"
                  and obj["usage"]["completion_tokens"] == chat_tokens,
                  f"chat: finish {choice['finish_reason']!r} usage {obj['usage']}")
        n_req += len(threads)

    # Two prompts sharing a prefix, in sequence: the second must hit the
    # on-device prefix index.
    before = metric_sum(parse_metrics(_get(port, "/metrics")),
                        "prefix_cache_hit_tokens_total")
    shared = _prompt(rng, traffic.prefix_len)
    for i in (0, 1):
        tail = _prompt(rng, 24 + 8 * i)
        res = stream_completion(port, {
            "prompt": shared + tail, "temperature": 0, "ignore_eos": True,
            "max_tokens": traffic.max_tokens}, traffic.timeout_s)
        _check_answer(f"prefix pair {i}", res, len(shared + tail),
                      traffic.max_tokens)
    n_req += 2
    metrics = parse_metrics(_get(port, "/metrics"))
    hit = metric_sum(metrics, "prefix_cache_hit_tokens_total") - before
    check(hit > 0, "prefix pair: prefix_cache_hit_tokens_total did not move")

    compiled = meter.since(mark)
    out = {"requests": n_req, "prefix_hit_tokens": hit,
           "seconds": round(time.monotonic() - t0, 2)}
    emit(phase="traffic", **out, memory=memory_stats(), **compiled)
    # Whatever compiles here compiled in the middle of serving, with every
    # live stream waiting behind it.
    check(compiled["compiles"] == 0,
          f"{compiled['compiles']} compilations during the traffic phase "
          f"({compiled['compile_s']} s): the warm-up left a program cold")
    return out


def check_metrics(pod: Pod, expect_labels: dict) -> dict:
    """After the traffic: the labels as /metrics exports them, no fault /
    recovery / quarantine (the engine retries a step that died, so 200s
    alone would hide a kernel that faults at run time), and evidence that
    the paths the labels name really ran."""
    metrics = parse_metrics(_get(pod.port, "/metrics"))
    info = metrics.get("engine_config_info") or [({}, 0.0)]
    labels = info[0][0]
    bad = {k: (labels.get(k), v) for k, v in expect_labels.items()
           if labels.get(k) != v}
    check(not bad, f"/metrics engine_config_info (got, want): {bad}")
    zeros = {n: metric_sum(metrics, n) for n in (
        "engine_faults_total", "requests_recovered_total",
        "requests_quarantined_total")}
    check(not any(zeros.values()), f"faults on the path: {zeros}")
    ran = {"generation_tokens_total":
           metric_sum(metrics, "generation_tokens_total"),
           "pipelined_issues":
           metric_sum(metrics, "pipeline_depth_occupancy_count"),
           "mixed_grid_steps_total":
           metric_sum(metrics, "mixed_grid_steps_total")}
    if int(labels.get("pipeline_depth", "0")):
        check(ran["pipelined_issues"] > 0,
              "pipeline_depth >= 1 but no dispatch was ever pipelined")
    if labels.get("mixed_step") == "true":
        check(ran["mixed_grid_steps_total"] > 0,
              "mixed_step=true but the mixed kernel's grid never counted")
    out = {"labels": labels, **zeros, **ran}
    emit(phase="metrics", **out)
    return out


def launch_plan(pod: Pod) -> dict:
    """What steers the mixed kernel: whether a tune table exists at the
    path the engine reads, and the grid plan resolved for this shape."""
    import os

    from arks_tpu.models import transformer as tf
    from arks_tpu.ops import autotune
    from arks_tpu.ops.paged_attention import mixed_grid_plan

    eng = pod.engine
    cfg = eng.cfg
    out = {"kernel_tune_mode": autotune.mode(),
           "kernel_tune_table": autotune.cache_path(),
           "kernel_tune_table_found": os.path.exists(autotune.cache_path())}
    if pod.labels.get("mixed_step") == "true":
        kvd = pod.labels["kv_dtype"]
        out["mixed_grid_plan"] = mixed_grid_plan(
            eng._mixed_budget + 1, hkv=cfg.num_kv_heads,
            g=cfg.num_heads // cfg.num_kv_heads,
            d=tf.cache_head_dim(cfg, eng._pad_head()), page=eng._page_size(),
            kv=kvd if kvd in ("int8", "int4") else str(eng._cache.k.dtype))
    emit(phase="launch_plan", **out)
    return out


def exercise(pod: Pod, expect_labels: dict, traffic: Traffic,
             meter: CompileMeter) -> dict:
    """Warm a built pod, drive the checked traffic, read /metrics."""
    launch_plan(pod)
    warm_up(pod, traffic, meter)
    out = drive_traffic(pod, traffic, meter)
    out.update(check_metrics(pod, expect_labels))
    return out


def run_pod(argv: list[str], expect_labels: dict, traffic: Traffic,
            meter: CompileMeter) -> dict:
    """Build, exercise, stop."""
    pod = build_pod(argv, expect_labels, meter)
    try:
        return exercise(pod, expect_labels, traffic, meter)
    finally:
        pod.close()


# ---------------------------------------------------------------------------
# Phase (four chips, --tp4 only): tensor parallelism against one device
# ---------------------------------------------------------------------------


def _greedy_ids(engine, prompt_ids: list[int], max_tokens: int,
                timeout_s: float) -> list[int]:
    from arks_tpu.engine.types import Request, SamplingParams
    req = Request("smoke-greedy", list(prompt_ids), SamplingParams(
        max_tokens=max_tokens, temperature=0.0, ignore_eos=True))
    engine.add_request(req)
    ids: list[int] = []
    while True:
        out = req.outputs.get(timeout=timeout_s)
        ids.extend(out.token_ids)
        if out.finished:
            check(out.finish_reason == "length",
                  f"greedy probe finished {out.finish_reason!r}: {out.error}")
            return ids


def _first_logits(engine, prompt_ids: list[int], f32: bool = False):
    """Next-token logits of the plain forward (``tf.prefill``) over the
    engine's own weights, sharded as the engine shards them.  With ``f32``
    the stored weights are widened inside the program and the matmuls run
    at the highest precision: the same function without bf16 rounding."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from arks_tpu.models import transformer as tf

    def forward(params, tokens, lengths):
        if f32:
            params = jax.tree.map(
                lambda x: x.astype(jnp.float32)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        return tf.prefill(params, engine.cfg, tokens, lengths,
                          mesh=engine.mesh)[0]

    with (jax.default_matmul_precision("highest") if f32
          else contextlib.nullcontext()):
        logits = jax.jit(forward)(
            engine.params, jnp.asarray([prompt_ids], jnp.int32),
            jnp.asarray([len(prompt_ids)], jnp.int32))
    return np.asarray(logits[0], np.float32)


def _rel_diff(got, ref) -> dict:
    """Distance of two logit vectors: rms of the difference over rms of the
    reference (two unrelated vectors score about 1.4), and the largest
    difference over the largest logit."""
    import numpy as np
    return {"rel_rms": float(np.sqrt(np.mean((got - ref) ** 2))
                             / np.sqrt(np.mean(ref ** 2))),
            "max_abs_diff": float(np.max(np.abs(got - ref))),
            "ref_max_abs": float(np.max(np.abs(ref)))}


def tp_compare(model: str, *, tp: int, num_slots: int, max_model_len: int,
               weight_dtype: str, labels_one: dict, labels_tp: dict,
               traffic: Traffic, parity: dict, meter: CompileMeter,
               probe_tokens: int = 64) -> dict:
    """The same server at tensor-parallel ``tp`` against a one-device
    engine on the same seed: the kernels under the mesh against the XLA
    path under the same mesh, placement of the weights, next-token logits
    of a fixed prompt in f32 and in bf16, the share of greedy tokens that
    agree, then traffic with zero faults."""
    import gc

    import numpy as np

    rng = random.Random(SEED + 2)
    prompt_ids = [2 + rng.randrange(256) for _ in range(32)]

    one = build_pod(server_argv(model, num_slots=num_slots,
                                max_model_len=max_model_len,
                                weight_dtype=weight_dtype, tp=1),
                    labels_one, meter)
    try:
        ref = {f32: _first_logits(one.engine, prompt_ids, f32)
               for f32 in (True, False)}
        ref_ids = _greedy_ids(one.engine, prompt_ids, probe_tokens,
                              traffic.timeout_s)
    finally:
        one.close()
    # Device 0 needs the room now: the sharded engine generates every
    # weight there before placing it.  A stopped engine holds nothing once
    # it is unreachable (InferenceEngine.stop joins its off-thread program
    # build, the last thing that kept it alive).
    del one
    gc.collect()
    freed = memory_stats()
    emit(phase="reference_freed", memory=freed)
    check(all(m["bytes_in_use"] < 2**30 for m in freed),
          "the stopped one-device engine still holds the device")

    pod = build_pod(server_argv(model, num_slots=num_slots,
                                max_model_len=max_model_len,
                                weight_dtype=weight_dtype, tp=tp),
                    labels_tp, meter)
    try:
        # Placement: the one-device engine is gone and the weights were
        # generated on device 0 before they were sharded; neither may
        # leave a whole copy behind.
        mem = memory_stats()
        if mem:
            used = [m["bytes_in_use"] for m in mem[:tp]]
            check(max(used) <= TP_BYTES_FACTOR * min(used),
                  f"bytes in use per device {used}: spread beyond "
                  f"{TP_BYTES_FACTOR}x (device 0 holds {used[0]})")
        kernel_parity(mesh=pod.engine.mesh, **parity)
        got = {f32: _first_logits(pod.engine, prompt_ids, f32)
               for f32 in (True, False)}
        check(all(np.isfinite(x).all() for x in got.values()),
              "tp logits not finite")
        ids = _greedy_ids(pod.engine, prompt_ids, probe_tokens,
                          traffic.timeout_s)
        f32_diff, bf16_diff = (_rel_diff(got[f], ref[f]) for f in (True, False))
        bf16_bound = tp_bf16_rel_bound(pod.engine.cfg.num_layers)
        res = {"tp": tp, "bytes_in_use": [m["bytes_in_use"] for m in mem],
               "logits_f32": f32_diff, "f32_rel_bound": TP_F32_REL_BOUND,
               "logits_bf16": bf16_diff, "bf16_rel_rms_bound": bf16_bound,
               "argmax_equal": bool(got[False].argmax() == ref[False].argmax()),
               "greedy_agree_share":
               sum(a == b for a, b in zip(ids, ref_ids)) / len(ref_ids)}
        emit(phase="tp_compare", **res)
        check(f32_diff["rel_rms"] <= TP_F32_REL_BOUND
              and f32_diff["max_abs_diff"]
              <= TP_F32_REL_BOUND * f32_diff["ref_max_abs"],
              f"tp={tp} next-token logits in f32 differ from one device's "
              f"by {f32_diff} (bound {TP_F32_REL_BOUND:.6f}): the sharded "
              f"program computes another function")
        check(bf16_diff["rel_rms"] <= bf16_bound,
              f"tp={tp} next-token logits in bf16 differ from one device's "
              f"by {bf16_diff} (rel_rms bound {bf16_bound:.4f})")
        # The serving path (paged pool, kernels, sharded or not) against
        # the plain forward: the first token either engine serves is among
        # the forward's five largest logits.  Equality would flip on a
        # near-tie; rank five does not.
        top5 = set(np.argsort(ref[False])[-5:].tolist())
        check(ref_ids[0] in top5 and ids[0] in top5,
              f"first served token (one device {ref_ids[0]}, tp {ids[0]}) "
              f"is not among the forward's top five {sorted(top5)}")
        res.update(exercise(pod, labels_tp, traffic, meter))
    finally:
        pod.close()
    return res


# ---------------------------------------------------------------------------
# The command
# ---------------------------------------------------------------------------

CHIP_TRAFFIC = Traffic(prompt_lens=(16, 128, 900), max_tokens=64, streams=12,
                       waves=2, prefix_len=512)
TP_TRAFFIC = dataclasses.replace(CHIP_TRAFFIC, streams=8, waves=1)
# The engine's serving shapes at qwen2.5-7b: 4 KV heads of 7 queries, head
# dim 128, 256-token pages, four pages of context, a 256-token chunk.
CHIP_PARITY = dict(hkv=4, g=7, d=128, page=256, max_pages=4, chunk=256,
                   decode_lanes=6)
# The benchmark's flood at its 192 slots: 115 streams decoding while the
# chunk's 256 rows are shared among 76 prompts (3-4 rows each).
CHIP_PARITY_FLOOD = dict(CHIP_PARITY, decode_lanes=115, chunk_lanes=76)
# A latent pool at Kimi-K2.5's widths (64 heads over one 512 + 64 row a
# token, stored 640 wide) under a flood-like lane mix: 12 streams decoding
# while the chunk's 256 rows are shared among 3 prompts.
CHIP_PARITY_LATENT = dict(heads=64, row=576, value=512, page=256, max_pages=4,
                          chunk=256, decode_lanes=12, chunk_lanes=3)


def run(tp4: bool) -> dict:
    t0 = time.monotonic()
    import jax
    dev = device_info()           # first touch of the backend
    emit(phase="device", jax=jax.__version__, backend_init_s=round(
        time.monotonic() - t0, 2), **dev)
    # The platform check, before anything is built: a process that came
    # up on the CPU would serve from interpret-mode kernels and pass.
    check(dev["platform"] == "tpu",
          f"no TPU: jax reports platform {dev['platform']!r}")
    meter = CompileMeter()
    if tp4:
        check(dev["count"] == 4, f"--tp4 needs 4 chips, found {dev['count']}")
        # A meshed engine resolves to depth 0: the pipelined programs have
        # only ever served single-device engines (engine.py, "The pipe
        # programs"; ROADMAP S11).
        tp_compare(MODEL, tp=4, num_slots=NUM_SLOTS,
                   max_model_len=MAX_MODEL_LEN, weight_dtype="int8",
                   labels_one={**TPU_LABELS, "tensor_parallel": "1"},
                   labels_tp={**TPU_LABELS, "tensor_parallel": "4",
                              "pipeline_depth": "0"},
                   traffic=TP_TRAFFIC, parity=dict(kv="int8", **CHIP_PARITY),
                   meter=meter)
    else:
        for kv in ("int8", "int4"):
            kernel_parity(kv=kv, **CHIP_PARITY)
        kernel_parity(kv="int8", **CHIP_PARITY_FLOOD)
        latent_parity(**CHIP_PARITY_LATENT)
        head_group_parity(kv="int8", **CHIP_PARITY)
        run_pod(server_argv(MODEL, num_slots=NUM_SLOTS,
                            max_model_len=MAX_MODEL_LEN, weight_dtype="int8",
                            tp=1),
                {**TPU_LABELS, "tensor_parallel": "1"}, CHIP_TRAFFIC, meter)
    emit(phase="total", seconds=round(time.monotonic() - t0, 2),
         **meter.since())
    return dev


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tp4", action="store_true",
                   help="four chips: run the tensor-parallel-4 server and "
                        "the one-device engine it is compared with, and no "
                        "other phase")
    args = p.parse_args(argv)
    # Logs go to stderr; stdout carries the JSON lines only.
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    try:
        dev = run(args.tp4)
    except BaseException as e:   # reported, never survived: exit 1
        traceback.print_exc()
        sys.stderr.flush()
        emit(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        return 1
    emit(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
