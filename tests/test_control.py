"""Control-plane tests: store semantics + the controller phase machines,
driven end-to-end with the fake gang driver (the envtest analogue, but with
behavior assertions the reference's scaffolded tests lack — SURVEY.md §4)."""

import os
import time

import pytest

from harness import wait_for  # noqa: E402

from arks_tpu.control import resources as res
from arks_tpu.control.manager import build_manager
from arks_tpu.control.store import Conflict, NotFound, Store
from arks_tpu.control.workloads import FakeGangDriver


# ---------------------------------------------------------------------------
# Store semantics
# ---------------------------------------------------------------------------

def test_store_crud_and_conflict():
    s = Store()
    m = res.Model(name="m1", spec={"model": "x"})
    s.create(m)
    got = s.get(res.Model, "m1")
    assert got.spec["model"] == "x"

    stale = s.get(res.Model, "m1")
    got.spec["model"] = "y"
    s.update(got)
    stale.spec["model"] = "z"
    with pytest.raises(Conflict):
        s.update(stale)


def test_store_finalizers_and_cascade():
    s = Store()
    app = res.Application(name="a1")
    s.create(app)
    s.add_finalizer(app, "test/finalizer")
    child = res.GangSet(name="g1", owner_refs=[("Application", "a1")])
    s.create(child)

    s.delete(res.Application, "a1")
    # Finalizer holds the object.
    held = s.get(res.Application, "a1")
    assert held.deletion_requested
    s.strip_finalizer(held, "test/finalizer")
    with pytest.raises(NotFound):
        s.get(res.Application, "a1")
    # Cascade removed the owned GangSet.
    with pytest.raises(NotFound):
        s.get(res.GangSet, "g1")


def test_store_watch_replays_and_streams():
    s = Store()
    s.create(res.Model(name="pre"))
    q = s.watch(res.Model)
    ev, obj = q.get(timeout=1)
    assert ev == "ADDED" and obj.name == "pre"
    s.create(res.Model(name="post"))
    ev, obj = q.get(timeout=1)
    assert ev == "ADDED" and obj.name == "post"


# ---------------------------------------------------------------------------
# Controller stack (fake driver)
# ---------------------------------------------------------------------------

@pytest.fixture()
def stack(tmp_path):
    driver = FakeGangDriver()
    mgr = build_manager(models_root=str(tmp_path / "models"), driver=driver)
    mgr.start()
    yield mgr, mgr.store, driver
    mgr.stop()


def test_model_existing_storage_ready(stack):
    mgr, store, _ = stack
    store.create(res.Model(name="m-exist", spec={"model": "org/m"}))
    assert mgr.wait_idle()
    m = store.get(res.Model, "m-exist")
    assert m.status["phase"] == res.MODEL_PHASE_READY
    assert m.condition(res.COND_STORAGE_CREATED)
    assert m.condition(res.COND_MODEL_LOADED)
    assert os.path.isdir(m.status["path"])
    # generateModelPath layout parity: <root>/models/<ns>/<name>
    assert m.status["path"].endswith("models/default/m-exist")


def test_model_local_source_download(stack, tmp_path):
    mgr, store, _ = stack
    src = tmp_path / "src-model"
    src.mkdir()
    (src / "weights.bin").write_bytes(b"w" * 32)
    store.create(res.Model(name="m-dl", spec={
        "model": "org/m", "source": {"local": {"path": str(src)}}}))
    assert mgr.wait_idle()
    m = store.get(res.Model, "m-dl")
    assert m.status["phase"] == res.MODEL_PHASE_READY
    assert os.path.exists(os.path.join(m.status["path"], "weights.bin"))


def test_model_bad_source_fails_with_message(stack):
    mgr, store, _ = stack
    store.create(res.Model(name="m-bad", spec={
        "model": "org/m", "source": {"local": {"path": "/does/not/exist"}}}))
    assert mgr.wait_idle()
    m = store.get(res.Model, "m-bad")
    assert m.status["phase"] == res.MODEL_PHASE_FAILED
    conds = {c["type"]: c for c in m.status["conditions"]}
    assert conds[res.COND_MODEL_LOADED]["status"] == "False"
    assert "/does/not/exist" in conds[res.COND_MODEL_LOADED]["message"]


def test_application_full_lifecycle(stack):
    mgr, store, driver = stack
    # App first: must wait in Loading until the model is Ready.
    store.create(res.Application(name="app1", spec={
        "replicas": 2, "runtime": "jax", "model": {"name": "m-app"},
        "servedModelName": "my-model", "tensorParallel": 1,
        "modelConfig": "tiny"}))
    assert mgr.wait_idle()
    app = store.get(res.Application, "app1")
    assert app.status["phase"] == res.PHASE_LOADING
    assert not app.condition(res.COND_LOADED)

    store.create(res.Model(name="m-app", spec={"model": "org/m"}))
    assert mgr.wait_idle()
    app = store.get(res.Application, "app1")
    assert app.status["phase"] == res.PHASE_RUNNING
    assert app.condition(res.COND_READY)
    assert app.status["readyReplicas"] == 2

    # Workload + Service exist with the reference naming/labels.
    gs = store.get(res.GangSet, "app1")
    assert gs.spec["replicas"] == 2
    assert "arks_tpu.server" in " ".join(gs.spec["leader"]["command"])
    svc = store.get(res.Service, "arks-application-app1")
    assert len(svc.status["addresses"]) == 2

    # Endpoint discovers the ready app.
    store.create(res.Endpoint(name="my-model", spec={"defaultWeight": 3}))
    assert mgr.wait_idle()
    ep = store.get(res.Endpoint, "my-model")
    routes = ep.status["routes"]
    assert len(routes) == 1
    assert routes[0]["weight"] == 3
    assert routes[0]["backend"]["service"] == "arks-application-app1"
    assert len(routes[0]["backend"]["addresses"]) == 2
    assert ep.status["match"] == {"namespace": "default", "model": "my-model"}

    # Group failure flips readiness; the route SURVIVES on the remaining
    # group (serving() semantics) but its address list shrinks — and the
    # app's phase reflects the degradation.
    driver.fail_group(gs.key, 0)
    wait_for(lambda: store.get(res.Application, "app1").status["readyReplicas"] == 1)
    app = store.get(res.Application, "app1")
    assert app.status["phase"] == res.PHASE_CREATING
    wait_for(lambda: len(store.get(res.Endpoint, "my-model")
                         .status["routes"][0]["backend"]["addresses"]) == 1)

    # ALL groups failing does drop the route.
    driver.fail_group(gs.key, 1)
    wait_for(lambda: store.get(res.Endpoint, "my-model").status["routes"] == [])

    driver.recover_group(gs.key, 0)
    driver.recover_group(gs.key, 1)
    wait_for(lambda: store.get(res.Application, "app1").status["phase"] == res.PHASE_RUNNING)

    # Deletion tears down the gang and cascades the service.
    store.delete(res.Application, "app1")
    wait_for(lambda: store.try_get(res.Application, "app1") is None)
    assert store.try_get(res.GangSet, "app1") is None
    assert store.try_get(res.Service, "arks-application-app1") is None
    assert ("default", "app1") in driver.torn_down


def test_application_invalid_runtime_fails(stack):
    mgr, store, _ = stack
    store.create(res.Application(name="bad-rt", spec={
        "runtime": "tensorrt", "model": {"name": "whatever"}}))
    assert mgr.wait_idle()
    app = store.get(res.Application, "bad-rt")
    assert app.status["phase"] == res.PHASE_FAILED
    conds = {c["type"]: c for c in app.status["conditions"]}
    assert conds[res.COND_PRECHECK]["status"] == "False"


def test_endpoint_static_routes_priority(stack):
    mgr, store, _ = stack
    store.create(res.Endpoint(name="static-ep", spec={
        "defaultWeight": 1,
        "routeConfigs": [{"backend": {"addresses": ["10.0.0.9:8080"]},
                          "weight": 7}]}))
    assert mgr.wait_idle()
    ep = store.get(res.Endpoint, "static-ep")
    assert ep.status["routes"][0]["static"] is True
    assert ep.status["routes"][0]["weight"] == 7


def test_rolling_spec_update_regenerates_workload(stack):
    mgr, store, _ = stack
    store.create(res.Model(name="m-roll", spec={"model": "org/m"}))
    store.create(res.Application(name="app-roll", spec={
        "replicas": 1, "runtime": "jax", "model": {"name": "m-roll"},
        "modelConfig": "tiny"}))
    assert mgr.wait_idle()
    app = store.get(res.Application, "app-roll")
    app.spec["replicas"] = 3
    store.update(app)
    assert mgr.wait_idle(timeout=10)
    gs = store.get(res.GangSet, "app-roll")
    assert gs.spec["replicas"] == 3
    assert store.get(res.Application, "app-roll").status["readyReplicas"] == 3


def test_rolling_update_sequential_and_route_survives(stack):
    """VERDICT acceptance: changing runtimeCommonArgs on a replicas=2 app
    restarts both groups sequentially (maxUnavailable=1, gated on the
    previous group's readiness) and the endpoint's backend list never goes
    empty during the rollout."""
    mgr, store, driver = stack
    store.create(res.Model(name="m-ru", spec={"model": "org/m"}))
    store.create(res.Application(name="app-ru", spec={
        "replicas": 2, "runtime": "jax", "model": {"name": "m-ru"},
        "servedModelName": "ru-model", "modelConfig": "tiny"}))
    store.create(res.Endpoint(name="ru-model", spec={}))
    assert mgr.wait_idle()
    wait_for(lambda: store.get(res.Application, "app-ru").status["readyReplicas"] == 2)
    gs_key = store.get(res.GangSet, "app-ru").key
    assert driver.restarts == []

    # Watch the endpoint's backends continuously during the rollout.
    import threading
    empties, stop = [], threading.Event()

    def watch():
        while not stop.is_set():
            ep = store.try_get(res.Endpoint, "ru-model")
            if ep is not None and ep.status.get("routes") is not None:
                if not ep.status["routes"]:
                    empties.append(True)
            time.sleep(0.01)

    t = threading.Thread(target=watch, daemon=True)
    t.start()
    try:
        app = store.get(res.Application, "app-ru")
        app.spec["runtimeCommonArgs"] = ["--max-model-len", "2048"]
        store.update(app)
        # Both groups roll, one at a time (driver records order).
        wait_for(lambda: len(driver.restarts) >= 2, timeout=30)
    finally:
        stop.set()
        t.join(timeout=5)

    assert driver.restarts[:2] == [(gs_key, 0), (gs_key, 1)]
    assert not empties, "endpoint backend list went empty during rollout"
    # New command propagated to the workload spec.
    gs = store.get(res.GangSet, "app-ru")
    assert "--max-model-len" in " ".join(gs.spec["leader"]["command"])
    wait_for(lambda: store.get(res.Application, "app-ru").status["readyReplicas"] == 2)


def test_pick_rolling_restart_semantics():
    from arks_tpu.control.workloads import pick_rolling_restart
    # No outdated groups -> nothing to do.
    assert pick_rolling_restart({0: "a", 1: "a"}, "a", {0: True, 1: True}) is None
    # All ready -> lowest outdated index first.
    assert pick_rolling_restart({0: "old", 1: "old"}, "new",
                                {0: True, 1: True}) == 0
    # Previous restart not ready yet -> hold (maxUnavailable=1).
    assert pick_rolling_restart({0: "new", 1: "old"}, "new",
                                {0: False, 1: True}) is None
    # Previous restart ready -> next one rolls.
    assert pick_rolling_restart({0: "new", 1: "old"}, "new",
                                {0: True, 1: True}) == 1
    # The candidate itself being unready does not block its own restart.
    assert pick_rolling_restart({0: "old", 1: "new"}, "new",
                                {0: False, 1: True}) == 0
    # A hung (alive-but-unready) outdated group rolls even when others are
    # unready too — restarting it can't reduce availability, and holding it
    # would wedge a corrective rollout forever.
    assert pick_rolling_restart({0: "old", 1: "old"}, "new",
                                {0: False, 1: False}) == 0
    assert pick_rolling_restart({0: "old", 1: "old"}, "new",
                                {0: True, 1: False}) == 1


# ---------------------------------------------------------------------------
# Autoscaler (native HPA analogue over gateway request rates)
# ---------------------------------------------------------------------------


def test_request_rate_tracker(monkeypatch):
    from arks_tpu.gateway import server as gws

    t = [960.0]  # exactly a minute boundary (minute 16)
    monkeypatch.setattr(gws.time, "time", lambda: t[0])
    tr = gws.RequestRateTracker()
    for _ in range(30):
        tr.record("ns", "m")
    # Same window: the 30 fresh requests count in full.
    assert tr.rpm("ns", "m") == 30
    # One window later at its midpoint: prev 30 weighted by the un-elapsed
    # half + 12 current.
    t[0] = 1050.0  # minute 17 + 30s
    for _ in range(12):
        tr.record("ns", "m")
    assert abs(tr.rpm("ns", "m") - (30 * 0.5 + 12)) < 1e-6
    # Two windows later: the old minutes have aged out entirely.
    t[0] = 1140.0  # minute 19
    assert tr.rpm("ns", "m") == 0
    assert tr.rpm("other", "m") == 0


def test_autoscaler_scales_up_then_down(tmp_path):
    import time as _time

    rpm = {"v": 500.0}
    driver = FakeGangDriver()
    mgr = build_manager(models_root=str(tmp_path / "models"), driver=driver,
                        rate_source=lambda ns, model: rpm["v"],
                        autoscale_interval_s=0.1)
    mgr.start()
    try:
        store = mgr.store
        store.create(res.Model(name="m1", spec={"model": "org/m"}))
        store.create(res.Application(name="auto", spec={
            "replicas": 1, "runtime": "jax", "model": {"name": "m1"},
            "servedModelName": "auto-m", "modelConfig": "tiny",
            "autoscale": {"minReplicas": 1, "maxReplicas": 3,
                          "targetRPMPerReplica": 100,
                          "scaleDownStabilizationSeconds": 1},
        }))
        deadline = _time.monotonic() + 20
        # 500 rpm / 100 target -> 5, clamped to max 3; scale-up immediate.
        while _time.monotonic() < deadline:
            app = store.get(res.Application, "auto")
            if app.spec.get("replicas") == 3:
                break
            _time.sleep(0.05)
        assert store.get(res.Application, "auto").spec["replicas"] == 3
        # Gang followed.
        gs = store.get(res.GangSet, "auto")
        assert gs.spec["replicas"] == 3

        # Demand drops; scale-down waits the stabilization window then lands
        # on the clamped minimum.
        rpm["v"] = 0.0
        t0 = _time.monotonic()
        while _time.monotonic() < deadline:
            app = store.get(res.Application, "auto")
            if app.spec.get("replicas") == 1:
                break
            _time.sleep(0.05)
        app = store.get(res.Application, "auto")
        assert app.spec["replicas"] == 1
        assert _time.monotonic() - t0 >= 0.9  # damped, not instant
        assert app.status["autoscale"]["desiredReplicas"] == 1
    finally:
        mgr.stop()


def test_autoscaler_splits_demand_across_peer_apps(tmp_path):
    """Multiple Applications behind one served name split the endpoint's
    demand — each must scale to its SHARE, not the full total."""
    import time as _time

    driver = FakeGangDriver()
    mgr = build_manager(models_root=str(tmp_path / "models"), driver=driver,
                        rate_source=lambda ns, model: 400.0,
                        autoscale_interval_s=0.1)
    mgr.start()
    try:
        store = mgr.store
        store.create(res.Model(name="m1", spec={"model": "org/m"}))
        for name in ("peer-a", "peer-b"):
            store.create(res.Application(name=name, spec={
                "replicas": 1, "runtime": "jax", "model": {"name": "m1"},
                "servedModelName": "shared-m", "modelConfig": "tiny",
                "autoscale": {"minReplicas": 1, "maxReplicas": 8,
                              "targetRPMPerReplica": 100,
                              # Short window: before both peers are
                              # serving(), shares are transiently too big
                              # and the test must not wait the 60s default
                              # to correct down.
                              "scaleDownStabilizationSeconds": 1},
            }))
        deadline = _time.monotonic() + 20
        while _time.monotonic() < deadline:
            reps = [store.get(res.Application, n).spec.get("replicas")
                    for n in ("peer-a", "peer-b")]
            if reps == [2, 2]:
                break
            _time.sleep(0.05)
        # 400 rpm / 2 peers = 200 each -> 2 replicas each (not 4).
        assert [store.get(res.Application, n).spec["replicas"]
                for n in ("peer-a", "peer-b")] == [2, 2]
    finally:
        mgr.stop()


def test_multislice_accelerator_maps_to_gang_and_flags(stack):
    """North-star config #5: a multi-slice accelerator spec
    ("tpu-v5p-16x2" = 2 slices x 2 hosts) sizes the gang to ALL hosts
    across slices, and the serve command carries --num-slices so the
    engine builds the DCN-crossing 'slice' mesh axis."""
    mgr, store, driver = stack
    store.create(res.Model(name="m-ms", spec={"model": "org/ms"}))
    store.create(res.Application(name="ms-app", spec={
        "replicas": 1, "runtime": "jax", "model": {"name": "m-ms"},
        "servedModelName": "ms-served", "tensorParallel": 4,
        "modelConfig": "tiny", "accelerator": "tpu-v5p-16x2"}))
    assert mgr.wait_idle()
    gs = store.get(res.GangSet, "ms-app")
    assert gs.spec["size"] == 4              # 2 hosts/slice x 2 slices
    cmd = " ".join(gs.spec["leader"]["command"])
    assert "--num-slices 2" in cmd
    assert gs.spec["accelerator"] == "tpu-v5p-16x2"

    # Single-slice shapes keep deriving size from the shape too.
    store.create(res.Application(name="ss-app", spec={
        "replicas": 1, "runtime": "jax", "model": {"name": "m-ms"},
        "servedModelName": "ss-served", "tensorParallel": 4,
        "modelConfig": "tiny", "accelerator": "tpu-v5e-16"}))
    assert mgr.wait_idle()
    gs2 = store.get(res.GangSet, "ss-app")
    assert gs2.spec["size"] == 4             # 4 hosts, one slice
    assert "--num-slices" not in " ".join(gs2.spec["leader"]["command"])

    # An explicit spec.size always wins over the shape derivation.
    store.create(res.Application(name="ovr-app", spec={
        "replicas": 1, "runtime": "jax", "model": {"name": "m-ms"},
        "servedModelName": "ovr-served", "size": 2,
        "modelConfig": "tiny", "accelerator": "tpu-v5e-16"}))
    assert mgr.wait_idle()
    assert store.get(res.GangSet, "ovr-app").spec["size"] == 2
