"""Build the serving pod the way ``python -m arks_tpu.server`` does, and the
small instruments around it.

``CompileMeter``, ``memory_stats`` and ``parse_metrics`` are copies of
``chip_smoke.py``'s (the yardstick lives under ``benchmarks/``; the
originals are listed in PERF.md).
"""

from __future__ import annotations

import http.client
import json
import os
import threading


class CompileMeter:
    """Counts XLA compilations and persistent-cache hits/misses through
    ``jax.monitoring`` (listeners run on whichever thread compiles)."""

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

    def mark(self) -> dict:
        with self._lock:
            return dict(self._n)

    def since(self, mark: dict | None = None) -> dict:
        now = self.mark()
        if mark:
            now = {k: now[k] - mark[k] for k in now}
        return now


def place_compile_cache() -> str | None:
    """The program's own placement (``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``.jax_compile_cache/`` in the checkout), called here because the
    reference's generators compile before ``build_engine`` would place it.
    Programs that compile in under a second are cached too: a run is a
    new process and meets every one of them again."""
    import jax
    from arks_tpu.utils import compile_cache
    path = compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest local device (None where the
    backend reports none, as the CPU does)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def memory_in_use_bytes() -> int | None:
    """Bytes held now on the fullest local device: after the build that is
    what the pod keeps resident (weights, pool, programs), which the peak
    does not say while the weight generators' float32 leaves stand in it."""
    import jax
    held = [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.local_devices()]
    held = [h for h in held if h is not None]
    return max(held) if held else None


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


def metric_sum(metrics: dict, name: str, **labels) -> float:
    return sum(v for lab, v in metrics.get(name, ())
               if all(lab.get(k) == want for k, want in labels.items()))


def scrape(port: int) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", "/metrics", headers={"Connection": "close"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"GET /metrics -> {resp.status}")
        return parse_metrics(resp.read().decode())
    finally:
        conn.close()


def complete(port: int, model: str, prompt: str, max_tokens: int,
             timeout: float = 600.0) -> dict:
    """One streamed completion, read to its end (warm-up only)."""
    body = json.dumps({"model": model, "prompt": prompt, "stream": True,
                       "stream_options": {"include_usage": True},
                       "max_tokens": max_tokens, "temperature": 0.0,
                       "ignore_eos": True})
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json",
                      "Connection": "close"})
        resp = conn.getresponse()
        data = resp.read().decode()
        usage = None
        for line in data.splitlines():
            if line.startswith("data: {"):
                usage = json.loads(line[6:]).get("usage") or usage
        return {"status": resp.status, "usage": usage}
    finally:
        conn.close()


class Pod:
    def __init__(self, engine, server) -> None:
        self.engine, self.server = engine, server

    @property
    def port(self) -> int:
        return self.server.port

    @property
    def labels(self) -> dict:
        return self.engine.resolved_config

    def close(self) -> None:
        self.server.stop()
        self.engine.stop()


# A seed the driver gives can pass 2**31; the server's --seed feeds int32
# sampler state, so the pod gets the seed folded into 31 bits.  Weights
# and traffic still differ from seed to seed.
def pod_seed(seed: int) -> int:
    return int(seed) % (2**31 - 1)


def build(config_name: str, config_dir: str, deploy: dict, seed: int,
          overrides: dict | None = None, platform: str | None = None) -> Pod:
    """Register the configuration from its ``config.json`` under its own
    name (so that ``--model <name>`` takes the program's seeded
    random-weight path; ``--model <dir>`` would ask for a checkpoint and
    fall back to a fixed key), then ``parse_args`` -> ``build_engine`` ->
    ``build_server`` -> ``start``, exactly the entry point's own steps."""
    for k, v in (deploy.get("env") or {}).items():
        os.environ[k] = str(v)
    from arks_tpu.models.config import ModelConfig, register_config
    from arks_tpu.server.__main__ import (build_engine, build_server,
                                          parse_args)

    register_config(ModelConfig.from_hf_config(config_dir, name=config_name))
    args = dict(deploy["server_args"], **(overrides or {}))
    argv = ["--model", config_name, "--seed", str(pod_seed(seed)),
            "--host", "127.0.0.1", "--port", "0"]
    for k, v in args.items():
        argv += [f"--{k}", str(v)]
    if platform:
        argv += ["--platform", platform]
    ns = parse_args(argv)
    engine = build_engine(ns)
    server = build_server(ns, engine)
    server.start(background=True)
    pod = Pod(engine, server)
    want = deploy.get("expect_labels") or {}
    bad = {k: (pod.labels.get(k), v) for k, v in want.items()
           if pod.labels.get(k) != v} if not overrides and not platform else {}
    if bad:
        pod.close()
        raise RuntimeError(f"engine_config_info (got, want): {bad}")
    return pod
