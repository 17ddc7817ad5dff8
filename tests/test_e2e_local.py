"""Full-stack e2e on one host: operator + REAL engine subprocess + gateway.

The "minimum end-to-end slice" (SURVEY.md §7 stage 4) plus the gateway:
manifests -> controllers -> LocalProcessDriver spawns a real
``python -m arks_tpu.server`` process -> Endpoint discovers it -> client
calls the gateway with a token and gets an engine-generated completion with
metered usage.
"""

import functools
import json
import time
import urllib.request

import pytest

from arks_tpu.control import resources as res
from arks_tpu.control.manager import build_manager
from arks_tpu.control.workloads import LocalProcessDriver
from arks_tpu.gateway.server import Gateway

import harness


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    root = tmp_path_factory.mktemp("e2e")
    driver = LocalProcessDriver(log_dir=str(root / "logs"))
    mgr = build_manager(models_root=str(root / "models"), driver=driver,
                        local_platform="cpu")
    mgr.start()
    gw = Gateway(mgr.store, host="127.0.0.1", port=0, quota_sync_s=0.5)
    gw.start(background=True)
    yield mgr, gw, driver
    gw.stop()
    mgr.stop()
    # Tear down spawned engines.
    for gs in mgr.store.list(res.GangSet):
        driver.teardown(gs)


# (a gang of processes comes up in tens of seconds: the harness's longest
# wait, polled at a process's pace)
wait_for = functools.partial(harness.wait_for, timeout=120.0, interval=0.25)


def test_quickstart_end_to_end(stack):
    mgr, gw, _driver = stack
    store = mgr.store

    store.create(res.Model(name="tiny-model", spec={"model": "test/tiny"}))
    store.create(res.Application(name="tiny-app", spec={
        "replicas": 1, "size": 1, "runtime": "jax",
        "model": {"name": "tiny-model"},
        "servedModelName": "tiny-served",
        "tensorParallel": 1,
        "modelConfig": "tiny",
        "runtimeCommonArgs": ["--num-slots", "2", "--max-model-len", "64"],
    }))
    store.create(res.Endpoint(name="tiny-served", spec={"defaultWeight": 1}))
    store.create(res.Token(name="e2e-user", spec={
        "token": "sk-e2e",
        "qos": [{"endpoint": {"name": "tiny-served"},
                 "rateLimits": [{"type": "rpm", "value": 50}],
                 "quota": {"name": "e2e-quota"}}]}))
    store.create(res.Quota(name="e2e-quota", spec={
        "quotas": [{"type": "total", "value": 100000}]}))

    # Engine subprocess boot: jax import + compile, tens of seconds on CPU.
    wait_for(lambda: store.get(res.Application, "tiny-app").status.get("phase")
             == res.PHASE_RUNNING, timeout=120)
    ep = wait_for(lambda: (store.get(res.Endpoint, "tiny-served").status.get("routes")
                           or None), timeout=30)
    assert ep[0]["backend"]["addresses"]

    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/chat/completions",
        data=json.dumps({
            "model": "tiny-served",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 5, "temperature": 0, "ignore_eos": True,
        }).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": "Bearer sk-e2e"})
    with urllib.request.urlopen(req, timeout=120) as r:
        data = json.load(r)
    assert data["object"] == "chat.completion"
    assert data["usage"]["completion_tokens"] == 5
    assert data["choices"][0]["finish_reason"] == "length"

    # Usage metered through the gateway into the quota service.
    total = data["usage"]["total_tokens"]
    assert gw.quota.get_usage("default", "e2e-quota")["total"] == total

    # Streamed request through the whole stack.
    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/chat/completions",
        data=json.dumps({
            "model": "tiny-served",
            "messages": [{"role": "user", "content": "again"}],
            "max_tokens": 4, "temperature": 0, "ignore_eos": True,
            "stream": True, "stream_options": {"include_usage": True},
        }).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": "Bearer sk-e2e"})
    frames = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data: "):
                frames.append(line[6:])
    assert frames[-1] == "[DONE]"
    wait_for(lambda: gw.quota.get_usage("default", "e2e-quota")["total"] > total,
             timeout=10)


def _launch_gang(store, name, served, extra_args=()):
    """Shared size-2 gang scaffolding: create the app + endpoint, wait for
    Running, return the leader address."""
    if store.try_get(res.Model, "gang-model") is None:
        store.create(res.Model(name="gang-model", spec={"model": "test/tiny"}))
    store.create(res.Application(name=name, spec={
        "replicas": 1, "size": 2, "runtime": "jax",
        "model": {"name": "gang-model"},
        "servedModelName": served,
        "tensorParallel": 2,
        "modelConfig": "tiny",
        "runtimeCommonArgs": ["--num-slots", "2", "--max-model-len", "64",
                              *extra_args],
    }))
    store.create(res.Endpoint(name=served, spec={"defaultWeight": 1}))
    # Two engine processes boot + distributed rendezvous + compile.
    wait_for(lambda: store.get(res.Application, name).status.get("phase")
             == res.PHASE_RUNNING, timeout=120)
    ep = wait_for(lambda: (store.get(res.Endpoint, served).status.get("routes")
                           or None), timeout=30)
    return ep[0]["backend"]["addresses"][0]


def _complete(addr, served, prompt, max_tokens):
    req = urllib.request.Request(
        f"http://{addr}/v1/completions",
        data=json.dumps({
            "model": served, "prompt": prompt,
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
        }).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.load(r)


def _assert_gang_alive(store, driver, name, members=2):
    time.sleep(2)
    gs = store.get(res.GangSet, name)
    group = driver._groups[gs.key][0]
    assert len(group.procs) == members
    assert all(p.poll() is None for p in group.procs)
    assert gs.status["readyReplicas"] == 1


def test_multiprocess_gang_serves(stack):
    """VERDICT acceptance: a size-2 gang launches BOTH members as real
    processes, they rendezvous via jax.distributed (gloo collectives over
    the 2-process CPU mesh), the leader broadcasts every dispatch to the
    follower, and the gang serves a real completion with tp=2 sharding
    spanning both processes."""
    mgr, gw, driver = stack
    store = mgr.store
    addr = _launch_gang(store, "gang-app", "gang-served")

    data = _complete(addr, "gang-served", "multi host", 6)
    assert data["usage"]["completion_tokens"] == 6
    assert data["choices"][0]["finish_reason"] == "length"

    # A second request exercises steady-state decode through the follower.
    data2 = _complete(addr, "gang-served", "again please", 4)
    assert data2["usage"]["completion_tokens"] == 4

    # The gang is really 2 live processes (leader + follower) and the
    # follower SURVIVES serving (a desync/crash there would show up as a
    # dead member and a group restart).
    _assert_gang_alive(store, driver, "gang-app")


def test_multiprocess_gang_with_spec_decode(stack):
    """A size-2 gang serving WITH speculative decoding: the leader
    broadcasts draft-prefill and spec dispatches, the follower mirrors
    them, and greedy output stays correct across the gang."""
    mgr, gw, driver = stack
    store = mgr.store
    addr = _launch_gang(store, "spec-gang", "spec-gang-served",
                        extra_args=["--draft-model", "tiny-gqa",
                                    "--draft-len", "4",
                                    "--prefix-cache-mb", "0"])

    data = _complete(addr, "spec-gang-served", "multi host spec", 6)
    assert data["usage"]["completion_tokens"] == 6

    # The spec path really fired on the gang (not a silent fused fallback).
    metrics = urllib.request.urlopen(f"http://{addr}/metrics",
                                     timeout=10).read().decode()
    prop = [l for l in metrics.splitlines()
            if l.startswith("spec_decode_proposed_tokens_total")]
    assert prop and float(prop[0].split()[-1]) > 0

    # Both processes alive after speculative serving.
    _assert_gang_alive(store, driver, "spec-gang")


def test_gang_member_death_restarts_group_and_serving_recovers(stack):
    """Failure detection e2e: killing a gang FOLLOWER mid-serving must take
    the whole group down (shared fate — the leader exits when its dispatch
    channel breaks rather than silently diverging), the driver restarts the
    gang, and serving recovers on the fresh processes.

    Reuses the gang from test_multiprocess_gang_serves (same module-scoped
    stack, runs after it in file order)."""
    mgr, gw, driver = stack
    store = mgr.store
    gs = store.get(res.GangSet, "gang-app")
    group = driver._groups[gs.key][0]
    old_procs = list(group.procs)
    assert all(p.poll() is None for p in old_procs)

    old_procs[1].kill()  # the follower

    # Shared fate + restart: eventually a NEW set of live processes.
    def regrouped():
        g = driver._groups.get(gs.key, [None])[0]
        if g is None or g.procs is old_procs:
            return False
        return (len(g.procs) == 2
                and all(p.poll() is None for p in g.procs)
                and all(p.pid != q.pid for p, q in zip(g.procs, old_procs)))
    wait_for(regrouped, timeout=60)

    # Readiness dips then recovers; the fresh gang serves.  The status and
    # route lag the restart (and the relaunch may bind a new port), so poll
    # the completion against the CURRENT route until it lands.
    def served_again():
        try:
            routes = store.get(res.Endpoint, "gang-served").status["routes"]
            if not routes or not routes[0]["backend"]["addresses"]:
                return False
            addr = routes[0]["backend"]["addresses"][0]
            data = _complete(addr, "gang-served", "after the restart", 4)
            return data["usage"]["completion_tokens"] == 4
        except Exception:
            return False

    wait_for(served_again, timeout=120, interval=2.0)


def test_follower_wedge_unreadies_gang_then_restarts(stack, monkeypatch):
    """Worker-wedge failure injection: SIGSTOP a gang FOLLOWER (alive but
    hung — the case member-death detection cannot see).  The follower's
    dispatch-channel heartbeat goes stale, the leader's /readiness flips
    503 within the bounded window (gang out of Service endpoints), and
    past the fatal deadline the leader exits so the driver restarts the
    whole group (the LWS RecreateGroupOnPodRestart behavior, extended to
    hangs)."""
    import os as _os
    import signal as _signal
    import urllib.error

    mgr, gw, driver = stack
    store = mgr.store
    # Env is inherited by the spawned gang processes (driver launches with
    # this process's environ): tight heartbeat/stale/fatal windows.
    monkeypatch.setenv("ARKS_GANG_HB_INTERVAL", "0.3")
    monkeypatch.setenv("ARKS_GANG_STALE_S", "2")
    monkeypatch.setenv("ARKS_GANG_WEDGE_FATAL_S", "10")
    addr = _launch_gang(store, "wedge-gang", "wedge-served")
    assert _complete(addr, "wedge-served", "pre-wedge", 4)[
        "usage"]["completion_tokens"] == 4

    gs = store.get(res.GangSet, "wedge-gang")
    group = driver._groups[gs.key][0]
    old_procs = list(group.procs)
    follower = old_procs[1]
    _os.kill(follower.pid, _signal.SIGSTOP)
    try:
        # Readiness flips within the stale window — the worker is alive
        # (not reaped) yet the gang must leave Service endpoints.
        def unready():
            assert follower.poll() is None  # still "alive" (stopped)
            try:
                urllib.request.urlopen(f"http://{addr}/readiness",
                                       timeout=5)
                return False
            except urllib.error.HTTPError as e:
                return e.code == 503 and b"heartbeat" in e.read()
            except Exception:
                return False
        wait_for(unready, timeout=30)

        # Escalation: leader exits past the fatal deadline, the driver
        # restarts the WHOLE group with fresh processes.
        def regrouped():
            g = driver._groups.get(gs.key, {}).get(0)
            if g is None or g.procs is old_procs:
                return False
            return (len(g.procs) == 2
                    and all(p.poll() is None for p in g.procs)
                    and all(p.pid != q.pid
                            for p, q in zip(g.procs, old_procs)))
        wait_for(regrouped, timeout=120)
    finally:
        if follower.poll() is None:
            _os.kill(follower.pid, _signal.SIGCONT)


def test_counter_store_outage_fails_cleanly():
    """A dead shared counter store (Redis down) must fail requests quickly
    and cleanly — bounded by the client's socket timeout — not hang the
    gateway's handler threads."""
    import urllib.error

    from arks_tpu.control.store import Store
    from arks_tpu.gateway.ratelimiter import RateLimiter
    from arks_tpu.gateway.rediskv import (
        RedisCounterBackend, RespClient, RespServer)
    from arks_tpu.gateway.server import Gateway

    # A live counter store at startup (RespClient fails fast on a bad
    # address by design) that dies mid-flight.
    resp = RespServer(host="127.0.0.1", port=0)
    resp.start(background=True)

    store = Store()
    store.create(res.Endpoint(name="m1", namespace="default", spec={},
                              status={"routes": []}))
    store.create(res.Token(name="t", namespace="default", spec={
        "token": "sk-t", "qos": [{"endpoint": {"name": "m1"}}]}))
    gw = Gateway(store, host="127.0.0.1", port=0,
                 rate_limiter=RateLimiter(RedisCounterBackend(
                     RespClient("127.0.0.1", resp.port, timeout_s=0.5))))
    gw.start(background=True)
    resp.stop()  # the outage
    try:
        wait_for(lambda: gw.qos.token_known("sk-t"), timeout=10)
        req = urllib.request.Request(
            f"http://127.0.0.1:{gw.port}/v1/chat/completions",
            data=json.dumps({"model": "m1", "messages": []}).encode(),
            headers={"Content-Type": "application/json",
                     "Authorization": "Bearer sk-t"})
        t0 = time.monotonic()
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError("expected an error response")
        except urllib.error.HTTPError as e:
            assert e.code >= 500  # clean server error, not a hang
        assert time.monotonic() - t0 < 10  # bounded by the socket timeout
    finally:
        gw.stop()
