"""Live-operator mode (control.live): the existing controllers driving a
(fake) Kubernetes apiserver — CRs in, owned StatefulSets/Services out,
status projected back, rolling updates sequenced across groups, deletion
finalizer-gated.  The envtest-tier behaviors the reference only scaffolds
(SURVEY.md §4)."""

import time

import pytest

from arks_tpu.control.k8s_client import ApiError, FakeKubeApi
from arks_tpu.control.live import FINALIZER, GV, LiveOperator

from harness import wait_for  # noqa: E402


@pytest.fixture()
def live(tmp_path):
    api = FakeKubeApi()
    op = LiveOperator(api, models_root=str(tmp_path / "models"),
                      interval_s=0.1)
    op.start()
    yield api, op
    op.stop()


def _cr(kind: str, name: str, spec: dict, ns: str = "default") -> dict:
    return {"apiVersion": GV, "kind": kind,
            "metadata": {"name": name, "namespace": ns}, "spec": spec}


def _mk_app(api, name="app1", replicas=2, served="m-served"):
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksapplications", "default", _cr(
        "ArksApplication", name, {
            "replicas": replicas, "size": 1, "runtime": "jax",
            "model": {"name": "m1"}, "servedModelName": served,
            "modelConfig": "tiny",
        }))


def _sts_names(api):
    return sorted(s["metadata"]["name"]
                  for s in api.list("apps/v1", "statefulsets"))


def _mark_ready(api, name, ready=1):
    api.patch("apps/v1", "statefulsets", "default", name,
              {"status": {"readyReplicas": ready}}, subresource="status")


def test_application_cr_to_statefulsets_and_back(live):
    """VERDICT acceptance: Application through the API -> StatefulSet/
    Service objects appear; readiness flows back into the CR's
    status.readyReplicas."""
    api, op = live
    _mk_app(api, replicas=2)

    wait_for(lambda: _sts_names(api) == ["arks-app1-0", "arks-app1-1"])
    svcs = sorted(s["metadata"]["name"] for s in api.list("v1", "services"))
    assert svcs == ["arks-app1-0", "arks-app1-1"]

    # Model went Ready (existing-storage path) and its status is projected.
    m = wait_for(lambda: api.get(GV, "arksmodels", "default", "m1"))
    wait_for(lambda: (api.get(GV, "arksmodels", "default", "m1")
                      .get("status", {}).get("phase")) == "Ready")

    # App not ready yet: no STS reports ready pods.
    app = api.get(GV, "arksapplications", "default", "app1")
    assert FINALIZER in app["metadata"]["finalizers"]
    wait_for(lambda: (api.get(GV, "arksapplications", "default", "app1")
                      .get("status", {}).get("phase")) == "Creating")

    _mark_ready(api, "arks-app1-0")
    _mark_ready(api, "arks-app1-1")
    wait_for(lambda: (api.get(GV, "arksapplications", "default", "app1")
                      .get("status", {}).get("readyReplicas")) == 2)
    assert (api.get(GV, "arksapplications", "default", "app1")
            ["status"]["phase"]) == "Running"


def test_endpoint_routes_projected(live):
    api, op = live
    _mk_app(api, served="ep-model")
    api.create(GV, "arksendpoints", "default",
               _cr("ArksEndpoint", "ep-model", {"defaultWeight": 2}))
    wait_for(lambda: _sts_names(api))
    for n in _sts_names(api):
        _mark_ready(api, n)
    routes = wait_for(lambda: (api.get(GV, "arksendpoints", "default", "ep-model")
                               .get("status", {}).get("routes")))
    assert routes[0]["weight"] == 2
    assert "arks-app1-0-0.arks-app1-0" in routes[0]["backend"]["addresses"][0]


def test_live_rolling_update_sequenced(live):
    """A spec change rolls ONE group's StatefulSet at a time, gated on the
    previous group reporting ready again (the cross-group maxUnavailable=1
    static manifests cannot express)."""
    api, op = live
    _mk_app(api, replicas=2)
    wait_for(lambda: len(_sts_names(api)) == 2)
    for n in _sts_names(api):
        _mark_ready(api, n)
    wait_for(lambda: (api.get(GV, "arksapplications", "default", "app1")
                      .get("status", {}).get("readyReplicas")) == 2)

    def revision(name):
        sts = api.get("apps/v1", "statefulsets", "default", name)
        return sts["spec"]["template"]["metadata"]["annotations"]["arks.ai/revision"]

    rev0 = revision("arks-app1-0")
    api.patch(GV, "arksapplications", "default", "app1",
              {"spec": {"runtimeCommonArgs": ["--max-model-len", "2048"]}})

    # Group 0 rolls first (the fake apiserver zeroes its readiness on the
    # template change, as the real controller-manager restart would)...
    wait_for(lambda: revision("arks-app1-0") != rev0)
    new_rev = revision("arks-app1-0")
    # ...and while it is not ready again, group 1 must HOLD the old revision.
    time.sleep(1.0)  # several reconcile cycles
    assert revision("arks-app1-1") == rev0

    # Group 0 back up -> group 1 rolls.
    _mark_ready(api, "arks-app1-0", ready=1)
    wait_for(lambda: revision("arks-app1-1") == new_rev)


def test_deletion_finalizer_gated(live):
    api, op = live
    _mk_app(api, replicas=1)
    wait_for(lambda: _sts_names(api) == ["arks-app1-0"])

    api.delete(GV, "arksapplications", "default", "app1")
    # Finalizer holds the CR until the store teardown removed the workload.
    wait_for(lambda: api.get(GV, "arksapplications", "default", "app1") is None)
    assert _sts_names(api) == []
    assert api.list("v1", "services") == []


def test_rendered_pods_carry_gang_contract(live):
    """Live-mode pods must match the gitops renderer's mechanics: models
    PVC mount, TPU nodeSelector/topology/chip requests via the shape
    table, and the jax.distributed env contract with per-pod process
    index — for a size>1 TPU gang."""
    api, op = live
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksapplications", "default", _cr(
        "ArksApplication", "tpuapp", {
            "replicas": 1, "size": 2, "runtime": "jax",
            "model": {"name": "m1"}, "servedModelName": "tpu-served",
            "modelConfig": "qwen2.5-7b", "accelerator": "tpu-v5p-16",
        }))
    sts = wait_for(lambda: api.get("apps/v1", "statefulsets", "default",
                                   "arks-tpuapp-0"))
    pod = sts["spec"]["template"]["spec"]
    assert pod["nodeSelector"] == {
        "cloud.google.com/gke-tpu-accelerator": "tpu-v5p-slice",
        "cloud.google.com/gke-tpu-topology": "2x2x2"}
    c = pod["containers"][0]
    assert c["resources"]["requests"]["google.com/tpu"] == "4"
    env = {e["name"]: e for e in c["env"]}
    assert env["ARKS_NUM_PROCESSES"]["value"] == "2"
    assert "pod-index" in str(env["ARKS_PROCESS_ID"]["valueFrom"])
    assert env["ARKS_COORDINATOR_ADDRESS"]["value"].startswith(
        "arks-tpuapp-0-0.arks-tpuapp-0")
    assert "ARKS_GANG_SECRET" in env
    # The SHARED models PVC (the one the operator downloads into) mounted
    # read-only at the reserved path.
    assert pod["volumes"][0]["persistentVolumeClaim"]["claimName"] == "models"
    assert c["volumeMounts"][0]["mountPath"] == "/models"


def test_force_removed_cr_tears_down(live):
    """A CR removed from the apiserver without our finalizer running (e.g.
    kubectl patch to strip finalizers) still tears down owned objects."""
    api, op = live
    _mk_app(api, replicas=1)
    wait_for(lambda: _sts_names(api) == ["arks-app1-0"])
    # Strip the finalizer and delete in one shot.
    api.patch(GV, "arksapplications", "default", "app1",
              {"metadata": {"finalizers": []}})
    api.delete(GV, "arksapplications", "default", "app1")
    assert api.get(GV, "arksapplications", "default", "app1") is None
    wait_for(lambda: _sts_names(api) == [])


def test_live_instance_spec_and_podgroup(live):
    """instanceSpec flows from the CR into live-rendered pods, and a
    podGroupPolicy yields a PodGroup with minMember = gang size plus the
    coscheduling pod label."""
    api, op = live
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksapplications", "default", _cr(
        "ArksApplication", "gapp", {
            "replicas": 1, "size": 2, "runtime": "jax",
            "model": {"name": "m1"}, "servedModelName": "g-served",
            "modelConfig": "tiny", "accelerator": "tpu-v5p-16",
            "instanceSpec": {
                "env": [{"name": "HF_HOME", "value": "/tmp/hf"}],
                "tolerations": [{"key": "google.com/tpu",
                                 "operator": "Exists"}],
            },
            "podGroupPolicy": {"kubeScheduling": {
                "scheduleTimeoutSeconds": 120}},
        }))
    sts = wait_for(lambda: api.get("apps/v1", "statefulsets", "default",
                                   "arks-gapp-0"))
    pod = sts["spec"]["template"]["spec"]
    env = {e["name"]: e.get("value") for e in pod["containers"][0]["env"]}
    assert env["HF_HOME"] == "/tmp/hf"
    assert pod["tolerations"][0]["key"] == "google.com/tpu"
    labels = sts["spec"]["template"]["metadata"]["labels"]
    assert labels["scheduling.x-k8s.io/pod-group"] == "arks-gapp-0"
    pg = wait_for(lambda: api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                                  "default", "arks-gapp-0"))
    assert pg["spec"]["minMember"] == 2
    assert pg["spec"]["scheduleTimeoutSeconds"] == 120

    # Gang-size changes must propagate into minMember — a stale value above
    # the real size would deadlock the coscheduling plugin forever.
    api.patch(GV, "arksapplications", "default", "gapp", {"spec": {"size": 1}})
    wait_for(lambda: api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                             "default", "arks-gapp-0")["spec"]["minMember"] == 1)

    # Removing the policy must delete the PodGroup, not orphan it.
    api.patch(GV, "arksapplications", "default", "gapp",
              {"spec": {"podGroupPolicy": None}})
    wait_for(lambda: api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                             "default", "arks-gapp-0") is None)


def test_live_invalid_instance_spec_fails_precheck(live):
    api, op = live
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksapplications", "default", _cr(
        "ArksApplication", "bad", {
            "replicas": 1, "size": 1, "runtime": "jax",
            "model": {"name": "m1"}, "servedModelName": "bad-served",
            "modelConfig": "tiny",
            "instanceSpec": {"volumes": [{"name": "models",
                                          "emptyDir": {}}]},
        }))
    wait_for(lambda: (api.get(GV, "arksapplications", "default", "bad")
                      .get("status", {}).get("phase")) == "Failed")
    conds = api.get(GV, "arksapplications", "default", "bad")["status"]["conditions"]
    pre = [c for c in conds if c["type"] == "Precheck"][0]
    assert pre["status"] == "False" and "reserved" in pre["message"]


def test_live_unified_disagg_unit_podgroup(live):
    """Unified layout in LIVE mode: every tier's pods join ONE unit-wide
    PodGroup whose minMember spans router + prefill + decode — not
    per-group PodGroups (reference generateUnifiedRBGS :1265-1326)."""
    api, op = live
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksdisaggregatedapplications", "default", _cr(
        "ArksDisaggregatedApplication", "updd", {
            "runtime": "jax", "model": {"name": "m1"},
            "servedModelName": "u-served", "modelConfig": "tiny",
            "mode": "unified",
            "podGroupPolicy": {"kubeScheduling": {}},
            "prefill": {"replicas": 1, "accelerator": "tpu-v5p-16"},  # 2 hosts
            "decode": {"replicas": 1},
            "router": {"replicas": 1},
        }))
    pg = wait_for(lambda: api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                                  "default", "arks-updd"))
    # 1 router + 1x2 prefill hosts + 1x1 decode host.
    assert pg["spec"]["minMember"] == 4
    # Tier pods carry the UNIT marker, and no per-group PodGroups exist.
    sts = api.get("apps/v1", "statefulsets", "default", "arks-updd-prefill-0")
    labels = sts["spec"]["template"]["metadata"]["labels"]
    assert labels["scheduling.x-k8s.io/pod-group"] == "arks-updd"
    for s in api.list("apps/v1", "statefulsets"):
        nm = s["metadata"]["name"]
        assert api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                       "default", nm) is None


def test_live_unified_to_legacy_cleans_unit_podgroup(live):
    """Switching a live disaggregated app from unified back to legacy must
    delete the unit-wide PodGroup (its large minMember would otherwise
    haunt the scheduler forever)."""
    api, op = live
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksdisaggregatedapplications", "default", _cr(
        "ArksDisaggregatedApplication", "sw", {
            "runtime": "jax", "model": {"name": "m1"},
            "servedModelName": "sw-served", "modelConfig": "tiny",
            "mode": "unified", "podGroupPolicy": {"kubeScheduling": {}},
            "prefill": {"replicas": 1}, "decode": {"replicas": 1},
            "router": {"replicas": 1},
        }))
    wait_for(lambda: api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                             "default", "arks-sw"))
    api.patch(GV, "arksdisaggregatedapplications", "default", "sw",
              {"spec": {"mode": "legacy"}})
    wait_for(lambda: api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                             "default", "arks-sw") is None)
    # Legacy per-group PodGroups take its place.
    wait_for(lambda: api.get("scheduling.x-k8s.io/v1alpha1", "podgroups",
                             "default", "arks-sw-prefill-0"))


def test_live_disagg_router_service_discovery(live):
    """Live-mode routers discover tier pods by label selector: the router
    gangset command carries --service-discovery, its pods bind the
    bootstrap ServiceAccount (Role/RoleBinding created like the reference's
    sglang-router RBAC), and tier pods carry the application/component
    labels the selector matches."""
    api, op = live
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksdisaggregatedapplications", "default", _cr(
        "ArksDisaggregatedApplication", "sd1", {
            "runtime": "jax", "model": {"name": "m1"},
            "servedModelName": "sd-served", "modelConfig": "tiny",
            "prefill": {"replicas": 1}, "decode": {"replicas": 1},
            "router": {"replicas": 1},
        }))
    router_sts = wait_for(lambda: api.get(
        "apps/v1", "statefulsets", "default", "arks-sd1-router-0"))
    tmpl = router_sts["spec"]["template"]
    c = tmpl["spec"]["containers"][0]
    args = c.get("command", []) + c.get("args", [])
    assert "--service-discovery" in args
    assert "--application" in args and "sd1" in args
    assert "--discovery-file" not in args
    assert tmpl["spec"]["serviceAccountName"] == "arks-sd1-router"
    # RBAC bootstrap (reference :530-596).
    assert api.get("v1", "serviceaccounts", "default", "arks-sd1-router")
    role = api.get("rbac.authorization.k8s.io/v1", "roles", "default",
                   "arks-sd1-router")
    assert {"pods"} == set(role["rules"][0]["resources"])
    assert api.get("rbac.authorization.k8s.io/v1", "rolebindings",
                   "default", "arks-sd1-router")
    # Tier pods carry the labels KubeDiscovery selects on.
    for tier in ("prefill", "decode"):
        sts = api.get("apps/v1", "statefulsets", "default",
                      f"arks-sd1-{tier}-0")
        labels = sts["spec"]["template"]["metadata"]["labels"]
        assert labels["arks.ai/application"] == "sd1"
        assert labels["arks.ai/component"] == tier


def test_watch_driven_propagation_and_bounded_requests():
    """VERDICT (round-2 item 6): watch streams drive ingest — a CR change
    propagates in well under the resync interval, with a BOUNDED number of
    apiserver requests per change (no per-tick full relists)."""
    api = FakeKubeApi()
    # Long intervals: if propagation relied on polling/resync, this test
    # would time out; only the watch path can deliver the spec in time.
    op = LiveOperator(api, models_root="/tmp/watch-models", interval_s=0.2,
                      resync_interval_s=3600.0)
    op.start()
    try:
        assert op.use_watch
        time.sleep(0.5)  # initial resync done; watchers armed
        api.create(GV, "arksmodels", "default",
                   _cr("ArksModel", "wm1", {"model": "org/m",
                                            "source": None}))
        t0 = time.monotonic()
        wait_for(lambda: op.store.try_get(
            __import__("arks_tpu.control.resources",
                       fromlist=["Model"]).Model, "wm1"), timeout=5)
        assert time.monotonic() - t0 < 2.0  # event latency, not resync
        # Spec UPDATE also rides the watch.
        api.patch(GV, "arksmodels", "default", "wm1",
                  {"spec": {"model": "org/m2"}})
        wait_for(lambda: op.store.get(
            __import__("arks_tpu.control.resources",
                       fromlist=["Model"]).Model, "wm1")
            .spec.get("model") == "org/m2", timeout=5)

        # Bounded request count: between changes, the operator must not
        # hammer the apiserver with full relists.  Allow status writes and
        # the pending watch re-opens; assert LISTS stay flat.
        time.sleep(0.5)
        lists_before = sum(1 for v, _ in api.actions if v == "list")
        time.sleep(2.0)
        lists_after = sum(1 for v, _ in api.actions if v == "list")
        assert lists_after - lists_before <= 2, (
            f"{lists_after - lists_before} lists in 2s of idle watch mode")
    finally:
        op.stop()


def test_poll_mode_still_works_without_watch():
    """APIs without watch support (use_watch=False) keep the old polling
    behavior end to end."""
    api = FakeKubeApi()
    op = LiveOperator(api, models_root="/tmp/poll-models", interval_s=0.1,
                      use_watch=False)
    op.start()
    try:
        assert not op.use_watch
        api.create(GV, "arksmodels", "default",
                   _cr("ArksModel", "pm1", {"model": "org/m"}))
        from arks_tpu.control.resources import Model
        wait_for(lambda: op.store.try_get(Model, "pm1"), timeout=5)
    finally:
        op.stop()


# ---------------------------------------------------------------------------
# Leader election (reference cmd/main.go:198-216) + health endpoints
# ---------------------------------------------------------------------------


def _mk_op(api, tmp_path, ident, lease_s=30.0, retry_s=0.05):
    # Default lease is deliberately LONG: on a loaded CI box (e2e gang
    # subprocesses from earlier test files can linger through teardown) a
    # starved elector thread must not lose its lease mid-test (the expiry
    # test passes its own short duration).
    from arks_tpu.control.leader import LeaderElector
    elector = LeaderElector(api, namespace="arks-system", identity=ident,
                            lease_duration_s=lease_s, retry_period_s=retry_s)
    return LiveOperator(api, models_root=str(tmp_path / ident),
                        interval_s=0.1, leader_elector=elector,
                        exit_on_lost_lease=False)


def test_leader_election_single_writer(tmp_path):
    """TWO operators against one apiserver: exactly one acquires the Lease
    and reconciles; the standby ingests NOTHING and writes nothing."""
    api = FakeKubeApi()
    a = _mk_op(api, tmp_path, "op-a")
    b = _mk_op(api, tmp_path, "op-b")
    a.start()
    wait_for(lambda: a.is_leader)
    b.start()
    try:
        _mk_app(api, replicas=1)
        wait_for(lambda: _sts_names(api) == ["arks-app1-0"])
        # Sustained: the standby never became leader, never started its
        # machinery, and its store saw nothing.
        time.sleep(0.5)
        assert a.is_leader and not b.is_leader
        assert a._machinery_started and not b._machinery_started
        from arks_tpu.control import resources as res
        assert b.store.list(res.Application) == []
        lease = api.get("coordination.k8s.io/v1", "leases", "arks-system",
                        "e4ada7ad.arks.ai")
        assert lease["spec"]["holderIdentity"] == "op-a"
    finally:
        b.stop()
        a.stop()


def test_leader_failover_on_graceful_release(tmp_path):
    """Stopping the leader RELEASES the lease; the standby takes over at
    its next retry and reconciles new CRs."""
    api = FakeKubeApi()
    a = _mk_op(api, tmp_path, "op-a")
    b = _mk_op(api, tmp_path, "op-b")
    a.start()
    wait_for(lambda: a.is_leader)
    b.start()
    try:
        _mk_app(api, replicas=1)
        wait_for(lambda: _sts_names(api) == ["arks-app1-0"])
        a.stop()
        wait_for(lambda: b.is_leader)
        wait_for(lambda: b._machinery_started)
        # The new leader reconciles: a second app materializes.
        api.create(GV, "arksapplications", "default", _cr(
            "ArksApplication", "app2", {
                "replicas": 1, "size": 1, "runtime": "jax",
                "model": {"name": "m1"}, "servedModelName": "m2",
                "modelConfig": "tiny"}))
        wait_for(lambda: "arks-app2-0" in _sts_names(api))
    finally:
        b.stop()
        a.stop()


def test_leader_failover_on_lease_expiry(tmp_path):
    """A CRASHED leader (no release) is replaced once its lease expires —
    the takeover path a wedged holder exercises."""
    api = FakeKubeApi()
    # 2s lease: long enough that suite-load starvation cannot pre-expire
    # it before the crash is simulated, short enough to keep the test
    # quick.  No wall-clock lower bound on the takeover — under load the
    # lease may already be near expiry when the elector stops; the EXPIRY
    # path is evidenced by the holder change + leaseTransitions instead.
    a = _mk_op(api, tmp_path, "op-a", lease_s=2.0)
    b = _mk_op(api, tmp_path, "op-b", lease_s=2.0)
    a.start()
    wait_for(lambda: a.is_leader)
    b.start()
    try:
        assert not b.is_leader  # held and unexpired: no steal
        # Simulate a crash: the elector thread dies WITHOUT releasing.
        a.elector.stop(release=False)
        a._stop_machinery()
        from arks_tpu.control.leader import _parse_rfc3339
        dead = api.get("coordination.k8s.io/v1", "leases", "arks-system",
                       "e4ada7ad.arks.ai")["spec"]
        expiry = (_parse_rfc3339(dead["renewTime"])
                  + dead["leaseDurationSeconds"])
        wait_for(lambda: b.is_leader, timeout=30.0)
        lease = api.get("coordination.k8s.io/v1", "leases", "arks-system",
                        "e4ada7ad.arks.ai")
        assert lease["spec"]["holderIdentity"] == "op-b"
        assert lease["spec"]["leaseTransitions"] >= 1
        # EXPIRY-gated, proven from the Lease's own timestamps (immune to
        # host scheduling noise): the takeover happened after the dead
        # leader's lease ran out, not as a steal of a live one.
        assert _parse_rfc3339(lease["spec"]["acquireTime"]) >= expiry
    finally:
        b.stop()
        a.stop()


def test_health_endpoints(tmp_path):
    """/healthz + /readyz over HTTP: leader live+ready; standby live but
    NOT ready (readiness gates the embedded gateway's Service endpoints to
    the leader — a standby's gateway would serve an empty store)."""
    import json
    import urllib.request

    from arks_tpu.control.live import HealthServer

    api = FakeKubeApi()
    a = _mk_op(api, tmp_path, "op-a")
    b = _mk_op(api, tmp_path, "op-b")
    ha = HealthServer(a, host="127.0.0.1", port=0)
    hb = HealthServer(b, host="127.0.0.1", port=0)
    ha.start()
    hb.start()
    a.start()
    wait_for(lambda: a.is_leader)
    b.start()
    try:
        import urllib.error

        def hit(port, path):
            try:
                r = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5)
                return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        for path in ("/healthz", "/readyz"):
            code, body = hit(ha.port, path)
            assert code == 200 and body["leader"] is True
        # Standby: live (healthz 200) but NOT ready (readyz 503) — the
        # gateway Service must route to the leader only.
        code, body = hit(hb.port, "/healthz")
        assert code == 200 and body["leader"] is False
        code, body = hit(hb.port, "/readyz")
        assert code == 503 and body["ok"] is False
        assert body["identity"] == "op-b"
        # Unknown path -> 404.
        import urllib.error
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{ha.port}/nope",
                                   timeout=5)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        hb.stop()
        ha.stop()
        b.stop()
        a.stop()


def test_metrics_endpoint_tokenreview_authenticated(tmp_path):
    """Operator /metrics: 401 without a bearer token, 403 on an invalid
    one, 200 + operator families for a TokenReview-valid token (the
    reference manager's authenticated metrics filter)."""
    import urllib.error
    import urllib.request

    from arks_tpu.control.live import HealthServer

    api = FakeKubeApi()
    api.valid_tokens.add("sa-prom-token")
    op = LiveOperator(api, models_root=str(tmp_path / "m"), interval_s=0.1)
    hs = HealthServer(op, host="127.0.0.1", port=0, metrics_auth_api=api)
    hs.start()
    op.start()
    try:
        _mk_app(api, replicas=1)
        wait_for(lambda: _sts_names(api) == ["arks-app1-0"])

        def hit(token=None):
            req = urllib.request.Request(
                f"http://127.0.0.1:{hs.port}/metrics",
                headers={"Authorization": f"Bearer {token}"} if token else {})
            try:
                r = urllib.request.urlopen(req, timeout=5)
                return r.status, r.read().decode()
            except urllib.error.HTTPError as e:
                return e.code, ""

        assert hit()[0] == 401
        assert hit("wrong-token")[0] == 403
        code, text = hit("sa-prom-token")
        assert code == 200
        assert "operator_sync_iterations_total" in text
        assert "operator_spec_ingests_total" in text
        assert 'operator_watch_events_total{' in text
        assert "operator_is_leader" in text

        # Probes stay unauthenticated (kubelet has no bearer token here).
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{hs.port}/healthz", timeout=5)
        assert r.status == 200
    finally:
        hs.stop()
        op.stop()


def test_token_review_over_http_apiserver():
    """KubeApi.token_review round-trips the TokenReview POST against the
    fake apiserver (the in-cluster call path)."""
    from arks_tpu.control.k8s_client import FakeApiServer, KubeApi

    srv = FakeApiServer()
    srv.start()
    try:
        srv.fake.valid_tokens.add("good")
        api = KubeApi(srv.url)
        assert api.token_review("good") is True
        assert api.token_review("bad") is False
    finally:
        srv.stop()
