"""Disaggregated (prefill/decode-separated) serving tests.

Covers the three layers the reference outsources to SGLang + its router
(SURVEY.md §2.4 "Prefill/Decode disaggregation"):
1. engine: detached prefill -> KV wire format -> prefilled admission is
   bit-identical to a unified run (greedy),
2. control plane: DisaggregatedApplication phase machine, 3 gangsets,
   router service, endpoint discovery (fake driver),
3. full stack: real prefill/decode/router subprocesses behind the gateway.
"""

import functools
import json
import time
import urllib.request

import numpy as np
import pytest

from arks_tpu.control import resources as res
from arks_tpu.control.manager import build_manager
from arks_tpu.control.workloads import FakeGangDriver, LocalProcessDriver
from arks_tpu.engine import kv_transfer
from arks_tpu.engine.engine import EngineConfig, InferenceEngine
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.engine.types import PrefilledState, Request, SamplingParams
from arks_tpu.gateway.server import Gateway
from arks_tpu.models import get_config

import harness  # noqa: E402

wait_for = functools.partial(harness.wait_for, interval=0.1)


def _a_reconcile_behind_finalize_makes_the_gangs_again(tmp) -> bool:
    """The controller's race at a deletion (ROADMAP D14), without a thread:
    a worker has READ the application, the deletion is requested, another
    worker finalizes and strips the finalizer, and then the first worker's
    ``reconcile`` runs on what it read.  True while nothing in ``reconcile``
    looks again: it makes the three gangs and the router's service a second
    time, and their owner is gone, so nobody deletes them."""
    from arks_tpu.control.disaggregated_controller import (
        DisaggregatedApplicationController as Ctl)
    from arks_tpu.control.store import Store
    store = Store()
    ctl = Ctl(store, discovery_dir=str(tmp))
    store.create(res.Model(name="m", spec={"model": "test/m"}))
    model = store.get(res.Model, "m")
    model.status["phase"] = res.MODEL_PHASE_READY
    store.update_status(model)
    store.create(res.DisaggregatedApplication(name="late", spec={
        "model": {"name": "m"}, "modelConfig": "tiny"}))
    ctl.reconcile(store.add_finalizer(
        store.get(res.DisaggregatedApplication, "late"), ctl.FINALIZER))
    read = store.get(res.DisaggregatedApplication, "late")
    store.delete(res.DisaggregatedApplication, "late")
    marked = store.get(res.DisaggregatedApplication, "late")
    ctl.finalize(marked)
    store.strip_finalizer(marked, ctl.FINALIZER)
    assert not store.list(res.GangSet) and not store.list(res.Service)
    ctl.reconcile(read)
    return bool(store.list(res.GangSet))


def _settled(store, name, tmp):
    """A STOP-GAP for the race above, which the two deletions below hit in
    three of four late runs of PR 50 (a reconcile of a status change still in
    flight when the test deletes): wait until no reconcile has written to the
    application for 0.3 s.  It goes with the defect: once ``reconcile`` no
    longer builds behind ``finalize`` the assertion says so, and this
    function and its two calls are deleted."""
    assert _a_reconcile_behind_finalize_makes_the_gangs_again(tmp), \
        "the controller is repaired (ROADMAP D14): delete _settled"
    seen = [None, time.monotonic()]

    def quiet():
        version = store.get(res.DisaggregatedApplication,
                            name).resource_version
        if version != seen[0]:
            seen[:] = [version, time.monotonic()]
        return time.monotonic() - seen[1] > 0.3
    wait_for(quiet, what=f"the reconciles of {name} to settle")


def test_a_reconcile_in_flight_at_the_deletion_builds_behind_finalize(
        tmp_path):
    """The reproduction, as the record of an OPEN defect of the program
    (ROADMAP D14; ``arks_tpu/`` was not PR 50's to edit).  The PR that
    repairs ``disaggregated_controller.py`` turns this assertion round."""
    assert _a_reconcile_behind_finalize_makes_the_gangs_again(tmp_path)


# ---------------------------------------------------------------------------
# 1. Engine-level KV handoff
# ---------------------------------------------------------------------------


def _ids_of(req: Request) -> list[int]:
    toks: list[int] = []
    while True:
        out = req.outputs.get(timeout=60)
        toks.extend(out.token_ids)
        if out.finished:
            return toks


def test_kv_transfer_roundtrip():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((2, 1, 8, 2, 4)).astype(np.float32)
    v = rng.standard_normal((2, 1, 8, 2, 4)).astype(np.float32)
    meta = {"first_token": 7, "num_prompt": 5, "seed": 3}
    buf = kv_transfer.pack(meta, [k, v])
    meta2, (k2, v2) = kv_transfer.unpack(buf)
    assert meta2 == meta
    np.testing.assert_array_equal(k, k2)
    np.testing.assert_array_equal(v, v2)


def test_kv_transfer_bfloat16():
    import jax.numpy as jnp

    k = np.asarray(jnp.arange(16, dtype=jnp.bfloat16).reshape(1, 1, 4, 1, 4))
    _, (k2,) = kv_transfer.unpack(kv_transfer.pack({}, [k]))
    assert str(k2.dtype) == "bfloat16"
    np.testing.assert_array_equal(np.asarray(k, np.float32),
                                  np.asarray(k2, np.float32))


def test_disaggregated_matches_unified():
    """Greedy prefill-on-A + decode-on-B == unified decode, token for token."""
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                        prefill_buckets=(16, 32), steps_per_dispatch=2)
    tok = ByteTokenizer()
    # Shared params: same seed => same init on both engines.
    unified = InferenceEngine(cfg, ecfg, tok)
    prompt = tok.encode("hello disaggregation")
    params = SamplingParams(max_tokens=8, temperature=0.0, ignore_eos=True)

    unified.start()
    try:
        ureq = Request(request_id="u1", prompt_ids=prompt, params=params)
        unified.add_request(ureq)
        expected = _ids_of(ureq)
    finally:
        unified.stop()

    prefill_engine = InferenceEngine(cfg, ecfg, tok)   # no decode loop
    decode_engine = InferenceEngine(cfg, ecfg, tok)
    pf = prefill_engine.prefill_detached(prompt, params)
    assert pf.num_prompt == len(prompt)

    # Through the wire format, as the servers would send it.
    meta, tensors = kv_transfer.unpack(kv_transfer.pack(
        {"first_token": pf.first_token, "num_prompt": pf.num_prompt,
         "seed": pf.seed}, [np.asarray(pf.k), np.asarray(pf.v)]))

    decode_engine.start()
    try:
        dreq = Request(request_id="d1", prompt_ids=[], params=params,
                       prefilled=PrefilledState(
                           first_token=meta["first_token"],
                           num_prompt=meta["num_prompt"],
                           seed=meta["seed"], k=tensors[0], v=tensors[1]))
        decode_engine.add_request(dreq)
        got = _ids_of(dreq)
    finally:
        decode_engine.stop()

    assert got == expected
    assert len(got) == 8


def test_disaggregated_guided_decoding():
    """Guided request across the disagg pair: the prefill engine samples
    the first token under the guide, the decode engine rebases the
    RELATIVE DFA row onto its own table (compiled in a different order
    here, to prove rebasing), and the full output matches the grammar."""
    import json as _json
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=96,
                        prefill_buckets=(16, 32), steps_per_dispatch=2)
    tok = ByteTokenizer()
    pat = r'\{"ok": (true|false)\}'
    params = SamplingParams(max_tokens=24, temperature=0.0,
                            guide=("regex", pat))
    prefill_engine = InferenceEngine(cfg, ecfg, tok)
    decode_engine = InferenceEngine(cfg, ecfg, tok)
    # Skew the decode engine's table layout: an unrelated guide compiled
    # FIRST shifts this guide's start_row vs the prefill engine's.
    decode_engine.guides.compile("regex", "[a-z]+")
    pf = prefill_engine.prefill_detached(tok.encode("zz"), params)
    g = prefill_engine.guides.lookup("regex", pat)
    assert 0 <= pf.guide_row < g.n_states

    decode_engine.start()
    try:
        dreq = Request(request_id="dg1", prompt_ids=[], params=params,
                       prefilled=PrefilledState(
                           first_token=pf.first_token,
                           num_prompt=pf.num_prompt, seed=pf.seed,
                           k=pf.k, v=pf.v, guide_row=pf.guide_row))
        decode_engine.add_request(dreq)
        got = _ids_of(dreq)
    finally:
        decode_engine.stop()
    text = tok.decode(got)  # _register_slot emits the first token too
    assert _json.loads(text)["ok"] in (True, False)


def test_admit_prefilled_refreshes_guide_tables():
    """Regression (advisor high-severity): _admit_prefilled set guide_row
    WITHOUT refreshing the device guide tables, unlike every other
    admission path — a guide published after the step's top-of-loop
    refresh (routine now that compiles finish on worker threads at
    arbitrary times, and the ordering tests/test_spec_decode.py followed
    by test_disagg.py::test_disaggregated_guided_decoding hit in one
    process) decoded against stale device rows: all -1 -> everything
    masked -> instant eos."""
    import json as _json
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=2, max_cache_len=96,
                        prefill_buckets=(16, 32), steps_per_dispatch=2)
    tok = ByteTokenizer()
    pat = r'\{"ok": (true|false)\}'
    params = SamplingParams(max_tokens=24, temperature=0.0,
                            guide=("regex", pat))
    prefill_engine = InferenceEngine(cfg, ecfg, tok)
    pf = prefill_engine.prefill_detached(tok.encode("zz"), params)

    decode_engine = InferenceEngine(cfg, ecfg, tok)
    decode_engine._ensure_guides_uploaded()  # the top-of-loop refresh
    # The guide publishes AFTER that refresh (what a worker-pool compile
    # finishing mid-step looks like): device tables are now stale.
    decode_engine.guides.compile(*params.guide)
    assert decode_engine._guide_ver != decode_engine.guides.version
    dreq = Request(request_id="rg1", prompt_ids=[], params=params,
                   prefilled=PrefilledState(
                       first_token=pf.first_token, num_prompt=pf.num_prompt,
                       seed=pf.seed, k=pf.k, v=pf.v,
                       guide_row=pf.guide_row))
    decode_engine.metrics.num_requests_waiting.inc(1)  # _preadmit decs
    assert decode_engine._preadmit(dreq) is None  # prefilled admits inline
    # THE regression check: the admission must have refreshed the device
    # tables before the slot's first decode dispatch.
    assert decode_engine._guide_ver == decode_engine.guides.version
    decode_engine.start()
    try:
        got = _ids_of(dreq)
    finally:
        decode_engine.stop()
    text = tok.decode(got)
    assert _json.loads(text)["ok"] in (True, False)


def test_detached_prefill_rejects_oversize_prompt():
    """The disaggregated prefill engine raises the typed rejection (the
    servers map it to HTTP 400 context_length_exceeded end-to-end, including
    across the decode server's KV pull)."""
    from arks_tpu.engine.engine import ContextLengthExceededError
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=1, max_cache_len=16,
                        prefill_buckets=(8,), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    with pytest.raises(ContextLengthExceededError):
        eng.prefill_detached(list(range(50)), SamplingParams())


def test_prefilled_too_long_is_aborted():
    cfg = get_config("tiny")
    ecfg = EngineConfig(model="tiny", num_slots=1, max_cache_len=16,
                        prefill_buckets=(8,), steps_per_dispatch=4)
    eng = InferenceEngine(cfg, ecfg, ByteTokenizer())
    eng.start()
    try:
        req = Request(request_id="big", prompt_ids=[], params=SamplingParams(),
                      prefilled=PrefilledState(
                          first_token=1, num_prompt=100, seed=0,
                          k=np.zeros((cfg.num_layers, 1, 8, cfg.num_kv_heads,
                                      cfg.head_dim), np.float32),
                          v=np.zeros((cfg.num_layers, 1, 8, cfg.num_kv_heads,
                                      cfg.head_dim), np.float32)))
        eng.add_request(req)
        out = req.outputs.get(timeout=30)
        assert out.finished and out.finish_reason == "abort"
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# 2. Control plane (fake driver)
# ---------------------------------------------------------------------------


@pytest.fixture()
def fake_stack(tmp_path):
    driver = FakeGangDriver()
    mgr = build_manager(models_root=str(tmp_path / "models"), driver=driver)
    mgr.start()
    yield mgr, driver
    mgr.stop()


def test_disaggregated_phase_machine(fake_stack, tmp_path):
    mgr, driver = fake_stack
    store = mgr.store
    store.create(res.Model(name="m", spec={"model": "test/m"}))
    store.create(res.DisaggregatedApplication(name="pd", spec={
        "model": {"name": "m"}, "servedModelName": "pd-served",
        "modelConfig": "tiny",
        "router": {"replicas": 1},
        "prefill": {"replicas": 1, "tensorParallel": 1},
        "decode": {"replicas": 2},
    }))

    wait_for(lambda: store.get(res.DisaggregatedApplication, "pd")
             .status.get("phase") == res.PHASE_RUNNING)
    app = store.get(res.DisaggregatedApplication, "pd")
    assert app.status["decode"]["readyReplicas"] == 2
    assert app.ready()

    # Three gangsets with the right commands.
    pre = store.get(res.GangSet, "pd-prefill")
    dec = store.get(res.GangSet, "pd-decode")
    rtr = store.get(res.GangSet, "pd-router")
    assert "--disaggregation-mode" in pre.spec["leader"]["command"]
    assert "prefill" in pre.spec["leader"]["command"]
    assert "decode" in dec.spec["leader"]["command"]
    assert "arks_tpu.router" in " ".join(rtr.spec["leader"]["command"])

    # Router service + endpoint discovery.
    svc = store.get(res.Service, "pd-router-svc")
    assert svc.spec["selector"][res.LABEL_ROLE] == "router"

    store.create(res.Endpoint(name="pd-served", spec={}))
    routes = wait_for(lambda: store.get(res.Endpoint, "pd-served")
                      .status.get("routes") or None)
    assert routes[0]["backend"]["service"] == "pd-router-svc"

    # Component failure flips readiness off.
    driver.fail_group(("default", "pd-decode"), 0)
    wait_for(lambda: not store.get(res.DisaggregatedApplication, "pd").ready())

    # Deleting the app cascades its workloads.
    _settled(store, "pd", tmp_path / "late")
    store.delete(res.DisaggregatedApplication, "pd")
    wait_for(lambda: store.try_get(res.GangSet, "pd-router") is None)


def test_disaggregated_tier_size_derives_from_accelerator(fake_stack, tmp_path):
    """Disagg tiers size their gangs from the accelerator shape exactly
    like the Application path (live and gitops renderings must agree):
    multi-host shapes set size, multi-slice ones add --num-slices, and
    the unified unit PodGroup counts every pod across slices."""
    mgr, driver = fake_stack
    store = mgr.store
    store.create(res.Model(name="m-acc", spec={"model": "test/m"}))
    store.create(res.DisaggregatedApplication(name="pda", spec={
        "mode": "unified",
        "model": {"name": "m-acc"}, "servedModelName": "pda-served",
        "modelConfig": "tiny",
        "podGroupPolicy": {"kubeScheduling": {}},
        "router": {"replicas": 1},
        "prefill": {"replicas": 1, "accelerator": "tpu-v5e-16"},
        "decode": {"replicas": 1, "accelerator": "tpu-v5p-16x2"},
    }))
    pre = wait_for(lambda: store.try_get(res.GangSet, "pda-prefill"))
    dec = wait_for(lambda: store.try_get(res.GangSet, "pda-decode"))
    assert pre.spec["size"] == 4                       # v5e-16: 4 hosts
    assert dec.spec["size"] == 4                       # 2 slices x 2 hosts
    assert "--num-slices 2" in " ".join(dec.spec["leader"]["command"])
    assert "--num-slices" not in " ".join(pre.spec["leader"]["command"])
    # Unit PodGroup spans router + all tier pods across slices: 1 + 4 + 4.
    assert pre.spec["podGroupUnit"]["minMember"] == 9
    wait_for(lambda: store.get(res.DisaggregatedApplication, "pda")
             .status.get("phase") == res.PHASE_RUNNING)
    _settled(store, "pda", tmp_path / "late")
    store.delete(res.DisaggregatedApplication, "pda")
    wait_for(lambda: store.try_get(res.GangSet, "pda-router") is None)


def test_disaggregated_rejects_non_jax_runtime(fake_stack):
    mgr, _ = fake_stack
    store = mgr.store
    store.create(res.DisaggregatedApplication(name="bad", spec={
        "runtime": "vllm", "model": {"name": "nope"}}))
    wait_for(lambda: store.get(res.DisaggregatedApplication, "bad")
             .status.get("phase") == res.PHASE_FAILED)


# ---------------------------------------------------------------------------
# 3. Full-stack e2e: real subprocesses + gateway
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pd_stack(tmp_path_factory):
    root = tmp_path_factory.mktemp("pd-e2e")
    driver = LocalProcessDriver(log_dir=str(root / "logs"))
    mgr = build_manager(models_root=str(root / "models"), driver=driver,
                        local_platform="cpu")
    mgr.start()
    gw = Gateway(mgr.store, host="127.0.0.1", port=0, quota_sync_s=0.5)
    gw.start(background=True)
    yield mgr, gw
    gw.stop()
    mgr.stop()
    for gs in mgr.store.list(res.GangSet):
        driver.teardown(gs)


def test_disaggregated_end_to_end(pd_stack):
    mgr, gw = pd_stack
    store = mgr.store

    store.create(res.Model(name="pd-model", spec={"model": "test/pd"}))
    store.create(res.DisaggregatedApplication(name="pd-app", spec={
        "model": {"name": "pd-model"}, "servedModelName": "pd-served",
        "modelConfig": "tiny",
        "router": {"replicas": 1},
        "prefill": {"replicas": 1,
                    "runtimeCommonArgs": ["--num-slots", "2",
                                          "--max-model-len", "64"]},
        "decode": {"replicas": 1,
                   "runtimeCommonArgs": ["--num-slots", "2",
                                         "--max-model-len", "64"]},
    }))
    store.create(res.Endpoint(name="pd-served", spec={}))
    store.create(res.Token(name="pd-user", spec={
        "token": "sk-pd",
        "qos": [{"endpoint": {"name": "pd-served"},
                 "rateLimits": [{"type": "rpm", "value": 50}]}]}))

    # Three subprocesses must boot (jax import + compile each).
    wait_for(lambda: store.get(res.DisaggregatedApplication, "pd-app")
             .status.get("phase") == res.PHASE_RUNNING, timeout=120,
             interval=0.5)
    wait_for(lambda: (store.get(res.Endpoint, "pd-served").status.get("routes")
                      or None), timeout=30, interval=0.25)

    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/chat/completions",
        data=json.dumps({
            "model": "pd-served",
            "messages": [{"role": "user", "content": "hello pd"}],
            "max_tokens": 6, "temperature": 0, "ignore_eos": True,
        }).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": "Bearer sk-pd"})
    with urllib.request.urlopen(req, timeout=120) as r:
        data = json.load(r)
    assert data["object"] == "chat.completion"
    assert data["usage"]["completion_tokens"] == 6
    assert data["choices"][0]["finish_reason"] == "length"

    # Streaming through router + decode + gateway.
    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/chat/completions",
        data=json.dumps({
            "model": "pd-served",
            "messages": [{"role": "user", "content": "stream pd"}],
            "max_tokens": 4, "temperature": 0, "ignore_eos": True,
            "stream": True, "stream_options": {"include_usage": True},
        }).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": "Bearer sk-pd"})
    frames = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for raw in r:
            line = raw.decode().strip()
            if line.startswith("data: "):
                frames.append(line[6:])
    assert frames[-1] == "[DONE]"
    usage_frames = [f for f in frames
                    if f != "[DONE]" and json.loads(f).get("usage")]
    assert usage_frames, "usage frame missing from disaggregated stream"
    assert json.loads(usage_frames[-1])["usage"]["completion_tokens"] == 4


# ---------------------------------------------------------------------------
# Router policy: cache_aware prefix affinity
# ---------------------------------------------------------------------------


def test_cache_aware_policy_pins_shared_prefixes():
    import json as _json

    from arks_tpu.router import Discovery, Router, _prefix_key, _rendezvous

    r = Router(Discovery(None), "m", policy="cache_aware")
    prefill = ["p1:1", "p2:1", "p3:1"]
    decode = ["d1:1", "d2:1"]
    sys_prompt = "You are a helpful assistant. " * 40  # > key window
    def body(user):
        return _json.dumps({"model": "m", "messages": [
            {"role": "system", "content": sys_prompt},
            {"role": "user", "content": user}]}).encode()

    picks = {r._pick(body(f"question {i}"), prefill, decode)
             for i in range(10)}
    # Same (long) system prompt -> same prefill AND decode every time,
    # regardless of the divergent user turn.
    assert len(picks) == 1

    # A different system prompt is free to land elsewhere; the key differs.
    k1 = _prefix_key(body("x"))
    k2 = _prefix_key(_json.dumps({"model": "m", "messages": [
        {"role": "system", "content": "Terse answers only. " * 40}]}).encode())
    assert k1 != k2

    # Rendezvous: removing an unrelated backend keeps the assignment.
    chosen = _rendezvous(k1, prefill)
    rest = [b for b in prefill if b != chosen]
    survivors = [b for b in prefill if b in ([chosen] + rest[:1])]
    assert _rendezvous(k1, survivors) == chosen


def test_round_robin_policy_spreads():
    import json as _json

    from arks_tpu.router import Discovery, Router

    r = Router(Discovery(None), "m", policy="round_robin")
    prefill = ["p1:1", "p2:1"]
    decode = ["d1:1", "d2:1"]
    b = _json.dumps({"model": "m", "prompt": "same"}).encode()
    picks = {r._pick(b, prefill, decode) for _ in range(4)}
    assert len(picks) == 2  # alternates


def test_prefix_key_robust_to_garbage():
    from arks_tpu.router import _prefix_key
    assert _prefix_key(b"not json") is None
    assert _prefix_key(b"{}") is None
    assert _prefix_key(b'{"messages": "nope"}') is None
    assert _prefix_key(b'{"prompt": "hi"}') is not None


def test_prefix_key_content_parts():
    """Content-part messages key on their serialized text parts; unknown
    content shapes stop the scan instead of skipping to a later turn."""
    import json as _json

    from arks_tpu.router import _prefix_key

    def body(messages):
        return _json.dumps({"model": "m", "messages": messages}).encode()

    tail = [{"role": "user", "content": "same tail question"}]
    parts_a = [{"role": "system", "content": [
        {"type": "text", "text": "persona A instructions"}]}] + tail
    parts_b = [{"role": "system", "content": [
        {"type": "text", "text": "persona B instructions"}]}] + tail
    ka, kb = _prefix_key(body(parts_a)), _prefix_key(body(parts_b))
    assert ka is not None and kb is not None and ka != kb
    # Same as the equivalent plain-string message.
    plain = [{"role": "system", "content": "persona A instructions"}] + tail
    assert _prefix_key(body(plain)) == ka

    # Unknown content shape in the FIRST message: never key on later turns.
    weird = [{"role": "system", "content": {"mystery": 1}}] + tail
    assert _prefix_key(body(weird)) is None


def test_prefix_key_content_parts_edge_shapes():
    """Null text values don't raise; image-only first messages don't key
    on later turns."""
    import json as _json

    from arks_tpu.router import _prefix_key

    def body(messages):
        return _json.dumps({"model": "m", "messages": messages}).encode()

    tail = [{"role": "user", "content": "tail"}]
    assert _prefix_key(body(
        [{"role": "u", "content": [{"type": "text", "text": None}]}] + tail
    )) is None
    assert _prefix_key(body(
        [{"role": "u", "content": [{"type": "image_url",
                                    "image_url": {"url": "x"}}]}] + tail
    )) is None


# ---------------------------------------------------------------------------
# Kubernetes label-selector service discovery (reference --service-discovery)
# ---------------------------------------------------------------------------


def _pod(name, app, role, ip, port, ready=True, phase="Running"):
    return {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": name, "namespace": "default",
                     "labels": {"arks.ai/application": app,
                                "arks.ai/component": role}},
        "spec": {"containers": [{"name": "engine",
                                 "ports": [{"containerPort": port}]}]},
        "status": {"phase": phase, "podIP": ip,
                   "conditions": [{"type": "Ready",
                                   "status": "True" if ready else "False"}]},
    }


def test_kube_discovery_selects_ready_leader_pods(monkeypatch):
    from arks_tpu.control.k8s_client import FakeKubeApi
    from arks_tpu.router import KubeDiscovery

    monkeypatch.delenv("ARKS_PREFILL_ADDRS", raising=False)
    monkeypatch.delenv("ARKS_DECODE_ADDRS", raising=False)
    api = FakeKubeApi()
    api.create("v1", "pods", "default", _pod("p0", "d1", "prefill", "10.0.0.1", 8080))
    api.create("v1", "pods", "default",
               _pod("p1", "d1", "prefill", "10.0.0.2", 8080, ready=False))
    api.create("v1", "pods", "default", _pod("d0", "d1", "decode", "10.0.0.3", 9090))
    api.create("v1", "pods", "default", _pod("x0", "OTHER", "decode", "10.0.0.4", 8080))
    api.create("v1", "pods", "default",
               _pod("d2", "d1", "decode", "10.0.0.5", 9090, phase="Pending"))

    disc = KubeDiscovery(api, "default", "d1", interval_s=0.0)
    prefill, decode = disc.backends()
    # Only READY Running pods of THIS app; addr = podIP:containerPort
    # (workers 503 their readiness, so only gang leaders appear).
    assert prefill == ["10.0.0.1:8080"]
    assert decode == ["10.0.0.3:9090"]

    # Pod churn is picked up on the next refresh.
    api.create("v1", "pods", "default", _pod("d3", "d1", "decode", "10.0.0.6", 9090))
    _, decode = disc.backends()
    assert decode == ["10.0.0.3:9090", "10.0.0.6:9090"]


def test_kube_discovery_env_fallback_until_pods_appear(monkeypatch):
    from arks_tpu.control.k8s_client import FakeKubeApi
    from arks_tpu.router import KubeDiscovery

    monkeypatch.setenv("ARKS_PREFILL_ADDRS", "svc-p:8080")
    monkeypatch.setenv("ARKS_DECODE_ADDRS", "svc-d:8080")
    api = FakeKubeApi()
    disc = KubeDiscovery(api, "default", "d1", interval_s=0.0)
    assert disc.backends() == (["svc-p:8080"], ["svc-d:8080"])
    api.create("v1", "pods", "default", _pod("p0", "d1", "prefill", "10.0.0.1", 8080))
    prefill, decode = disc.backends()
    assert prefill == ["10.0.0.1:8080"]   # discovered pods replace env
    assert decode == ["svc-d:8080"]       # tier without pods keeps fallback


def test_kube_discovery_prefers_http_named_port(monkeypatch):
    """A metrics port declared first (or a sidecar container ordered first)
    must not hijack routing: the port named ``http`` wins; with several
    unnamed ports and no ``http``, fall back to backend_port."""
    from arks_tpu.control.k8s_client import FakeKubeApi
    from arks_tpu.router import KubeDiscovery

    monkeypatch.delenv("ARKS_PREFILL_ADDRS", raising=False)
    monkeypatch.delenv("ARKS_DECODE_ADDRS", raising=False)
    api = FakeKubeApi()
    pod = _pod("p0", "d1", "prefill", "10.0.0.1", 9999)
    pod["spec"]["containers"] = [
        {"name": "sidecar", "ports": [{"containerPort": 9400,
                                       "name": "metrics"}]},
        {"name": "engine", "ports": [{"containerPort": 9999},
                                     {"containerPort": 8081, "name": "http"}]},
    ]
    api.create("v1", "pods", "default", pod)
    amb = _pod("d0", "d1", "decode", "10.0.0.2", 9999)
    amb["spec"]["containers"] = [
        {"name": "engine", "ports": [{"containerPort": 9400},
                                     {"containerPort": 9999}]}]
    api.create("v1", "pods", "default", amb)
    met = _pod("d1p", "d1", "decode", "10.0.0.3", 9400)
    met["spec"]["containers"] = [
        {"name": "engine", "ports": [{"containerPort": 9400,
                                      "name": "metrics"}]}]
    api.create("v1", "pods", "default", met)

    disc = KubeDiscovery(api, "default", "d1", backend_port=8080,
                         interval_s=0.0)
    prefill, decode = disc.backends()
    assert prefill == ["10.0.0.1:8081"]   # named http beats declared order
    # Ambiguous unnamed pair AND a lone named-metrics port both fall back.
    assert decode == ["10.0.0.2:8080", "10.0.0.3:8080"]


def test_router_with_kube_discovery_end_to_end():
    """A real Router using KubeDiscovery against a (fake) apiserver routes
    to real in-process prefill/decode servers discovered as pods — the
    live-mode deployment shape, minus the kubelet."""
    import urllib.error

    from arks_tpu.control.k8s_client import FakeApiServer, FakeKubeApi, KubeApi
    from arks_tpu.router import KubeDiscovery, Router
    from arks_tpu.server.disagg import DecodeServer, PrefillServer

    cfg = get_config("tiny")

    def eng(**kw):
        return InferenceEngine(
            cfg, EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                              prefill_buckets=(16, 32),
                              steps_per_dispatch=2), ByteTokenizer(), **kw)

    pre_e, dec_e = eng(), eng()
    dec_e.start()
    pre = PrefillServer(pre_e, served_model_name="t", host="127.0.0.1", port=0)
    dec = DecodeServer(dec_e, served_model_name="t", host="127.0.0.1", port=0)
    pre.start(background=True)
    dec.start(background=True)

    fake = FakeKubeApi()
    srv = FakeApiServer(fake)
    srv.start()
    url = srv.url
    fake.create("v1", "pods", "default",
                _pod("pre-0", "dapp", "prefill", "127.0.0.1", pre.port))
    fake.create("v1", "pods", "default",
                _pod("dec-0", "dapp", "decode", "127.0.0.1", dec.port))

    disc = KubeDiscovery(KubeApi(url), "default", "dapp", interval_s=0.0)
    router = Router(disc, "t", host="127.0.0.1", port=0)
    router.start(background=True)
    try:
        body = json.dumps({"model": "t", "prompt": "hi there", "max_tokens": 6,
                           "temperature": 0, "ignore_eos": True}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        out = json.load(urllib.request.urlopen(req, timeout=60))
        assert out["usage"]["completion_tokens"] == 6
        assert out["choices"][0]["text"]
    finally:
        router.stop()
        pre.stop()
        dec.stop()
        dec_e.stop()
        srv.stop()


def test_disagg_logprobs_match_unified():
    """A disaggregated logprob request returns the SAME logprob stream as
    the unified path (first token from the transferred PrefilledState, the
    rest from the decode side's own dispatches) — round-2 VERDICT hole."""
    import urllib.request as _url

    from arks_tpu.server import OpenAIServer
    from arks_tpu.server.disagg import DecodeServer, PrefillServer

    cfg = get_config("tiny")

    def eng():
        return InferenceEngine(
            cfg, EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                              prefill_buckets=(16, 32),
                              steps_per_dispatch=2), ByteTokenizer())

    uni_e, pre_e, dec_e = eng(), eng(), eng()
    uni_e.start()
    dec_e.start()
    uni = OpenAIServer(uni_e, served_model_name="t", host="127.0.0.1", port=0)
    pre = PrefillServer(pre_e, served_model_name="t", host="127.0.0.1", port=0)
    dec = DecodeServer(dec_e, served_model_name="t", host="127.0.0.1", port=0)
    for s in (uni, pre, dec):
        s.start(background=True)

    body = {"model": "t", "prompt": "logprob parity", "max_tokens": 5,
            "temperature": 0, "ignore_eos": True, "logprobs": 2, "seed": 7}

    def post(port, path, headers=None):
        req = _url.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json", **(headers or {})})
        return json.load(_url.urlopen(req, timeout=60))

    try:
        ref = post(uni.port, "/v1/completions")["choices"][0]
        got = post(dec.port, "/v1/disagg/completions",
                   {"X-Arks-Prefill-Addr": f"127.0.0.1:{pre.port}"})["choices"][0]
    finally:
        for s in (uni, pre, dec):
            s.stop()
        uni_e.stop()
        dec_e.stop()

    assert got["text"] == ref["text"]
    glp, rlp = got["logprobs"], ref["logprobs"]
    assert glp["tokens"] == rlp["tokens"]
    assert glp["text_offset"] == rlp["text_offset"]
    assert len(glp["token_logprobs"]) == 5
    for a, b in zip(glp["token_logprobs"], rlp["token_logprobs"]):
        assert abs(a - b) < 1e-3
    for da, db in zip(glp["top_logprobs"], rlp["top_logprobs"]):
        assert set(da) == set(db)


def test_disagg_prefill_on_gang_dispatcher():
    """Detached prefill on a multi-host gang: the dispatch is mirrored to
    followers (prefill_detached ops) instead of raising — round-2 VERDICT
    hole.  (The real 2-process gang path rides test_e2e_local's gang
    tests; here a recording dispatcher proves the emit contract.)"""
    class RecordingDispatcher:
        def __init__(self):
            self.ops = []

        def broadcast(self, op, payload):
            self.ops.append(op)

    cfg = get_config("tiny")
    eng = InferenceEngine(
        cfg, EngineConfig(model="tiny", num_slots=2, max_cache_len=64,
                          prefill_buckets=(16, 32), steps_per_dispatch=2),
        ByteTokenizer())
    eng.dispatcher = RecordingDispatcher()
    pf = eng.prefill_detached([3, 4, 5], SamplingParams(temperature=0.0))
    assert pf.num_prompt == 3 and pf.first_lp is None
    pf2 = eng.prefill_detached([3, 4, 5],
                               SamplingParams(temperature=0.0, logprobs=1))
    assert pf2.first_lp is not None
    assert pf2.first_token == pf.first_token
    assert eng.dispatcher.ops == ["prefill_detached", "prefill_detached_lp"]


def test_disaggregated_gang_prefill_e2e(pd_stack):
    """VERDICT acceptance (round-2 item 4): a size-2 multi-process PREFILL
    gang serves the PD path — detached prefills are mirrored to the gang
    follower (prefill_detached ops) and the transferred KV decodes
    correctly, including logprobs on the continuation."""
    mgr, gw = pd_stack
    store = mgr.store

    store.create(res.Model(name="pdg-model", spec={"model": "test/pdg"}))
    store.create(res.DisaggregatedApplication(name="pdg-app", spec={
        "model": {"name": "pdg-model"}, "servedModelName": "pdg-served",
        "modelConfig": "tiny",
        "router": {"replicas": 1},
        "prefill": {"replicas": 1, "size": 2, "tensorParallel": 2,
                    "runtimeCommonArgs": ["--num-slots", "2",
                                          "--max-model-len", "64"]},
        "decode": {"replicas": 1,
                   "runtimeCommonArgs": ["--num-slots", "2",
                                         "--max-model-len", "64"]},
    }))
    store.create(res.Endpoint(name="pdg-served", spec={}))
    store.create(res.Token(name="pdg-user", spec={
        "token": "sk-pdg",
        "qos": [{"endpoint": {"name": "pdg-served"},
                 "rateLimits": [{"type": "rpm", "value": 50}]}]}))

    # Four subprocesses boot (router + 2-process prefill gang + decode).
    wait_for(lambda: store.get(res.DisaggregatedApplication, "pdg-app")
             .status.get("phase") == res.PHASE_RUNNING, timeout=120,
             interval=0.5)
    wait_for(lambda: (store.get(res.Endpoint, "pdg-served")
                      .status.get("routes") or None), timeout=30,
             interval=0.25)

    req = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}/v1/completions",
        data=json.dumps({
            "model": "pdg-served", "prompt": "gang prefill",
            "max_tokens": 5, "temperature": 0, "ignore_eos": True,
            "logprobs": 1,
        }).encode(),
        headers={"Content-Type": "application/json",
                 "Authorization": "Bearer sk-pdg"})
    with urllib.request.urlopen(req, timeout=120) as r:
        data = json.load(r)
    assert data["usage"]["completion_tokens"] == 5
    lp = data["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 5  # incl. the transferred first token
    assert all(v <= 0 for v in lp["token_logprobs"])

    # Second request exercises the steady-state gang (follower mirrored a
    # full prefill cycle and survived).
    with urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{gw.port}/v1/completions",
            data=json.dumps({
                "model": "pdg-served", "prompt": "again",
                "max_tokens": 3, "temperature": 0, "ignore_eos": True,
            }).encode(),
            headers={"Content-Type": "application/json",
                     "Authorization": "Bearer sk-pdg"}), timeout=120) as r:
        assert json.load(r)["usage"]["completion_tokens"] == 3
