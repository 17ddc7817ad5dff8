"""Cluster-e2e tier without a cluster: the live operator drives a fake
apiserver OVER REAL HTTP through the production KubeApi client — the wire
protocol (URL building, merge-patch content types, status subresource,
error mapping) is exercised end to end, the role the reference's Kind
suite plays (test/e2e/e2e_test.go:45-270)."""


import pytest

from arks_tpu.control.k8s_client import ApiError, FakeApiServer, KubeApi
from arks_tpu.control.live import FINALIZER, GV, LiveOperator

from harness import wait_for  # noqa: E402


@pytest.fixture()
def http_world(tmp_path):
    srv = FakeApiServer()
    srv.start()
    api = KubeApi(srv.url)
    op = LiveOperator(api, models_root=str(tmp_path / "models"),
                      interval_s=0.1)
    op.start()
    yield api, srv
    op.stop()
    srv.stop()


def _cr(kind, name, spec, ns="default"):
    return {"apiVersion": GV, "kind": kind,
            "metadata": {"name": name, "namespace": ns}, "spec": spec}


def test_http_wire_roundtrip(http_world):
    """Client-level semantics over the real wire: create / get / list /
    merge-patch (incl. null-deletes and the status subresource) / replace /
    404 mapping."""
    api, _ = http_world
    api.create("apps/v1", "statefulsets", "ns1", {
        "metadata": {"name": "s1"}, "spec": {"replicas": 2, "extra": "x"}})
    obj = api.get("apps/v1", "statefulsets", "ns1", "s1")
    assert obj["spec"]["replicas"] == 2
    # Merge-patch: null deletes a key.
    api.patch("apps/v1", "statefulsets", "ns1", "s1",
              {"spec": {"extra": None, "replicas": 3}})
    obj = api.get("apps/v1", "statefulsets", "ns1", "s1")
    assert obj["spec"] == {"replicas": 3}
    # Status subresource only touches .status.
    api.patch("apps/v1", "statefulsets", "ns1", "s1",
              {"status": {"readyReplicas": 3}}, subresource="status")
    obj = api.get("apps/v1", "statefulsets", "ns1", "s1")
    assert obj["status"]["readyReplicas"] == 3 and obj["spec"]["replicas"] == 3
    # Replace (PUT) drops unspecified spec keys.
    obj["spec"] = {"replicas": 1}
    api.replace("apps/v1", "statefulsets", "ns1", "s1", obj)
    assert api.get("apps/v1", "statefulsets", "ns1", "s1")["spec"] == {"replicas": 1}
    # 404 mapping: get -> None, delete -> swallowed, create conflict -> 409.
    assert api.get("apps/v1", "statefulsets", "ns1", "nope") is None
    api.delete("apps/v1", "statefulsets", "ns1", "nope")
    try:
        api.create("apps/v1", "statefulsets", "ns1", {"metadata": {"name": "s1"}})
        raise AssertionError("expected 409")
    except ApiError as e:
        assert e.status == 409
    assert [o["metadata"]["name"]
            for o in api.list("apps/v1", "statefulsets", "ns1")] == ["s1"]


def test_http_operator_end_to_end(http_world):
    """Full loop over HTTP: CRs in -> owned StatefulSets/Services out,
    readiness back into CR status, finalizer-gated deletion."""
    api, _ = http_world
    api.create(GV, "arksmodels", "default",
               _cr("ArksModel", "m1", {"model": "org/m"}))
    api.create(GV, "arksapplications", "default", _cr(
        "ArksApplication", "webapp", {
            "replicas": 2, "size": 1, "runtime": "jax",
            "model": {"name": "m1"}, "servedModelName": "web-served",
            "modelConfig": "tiny",
        }))

    def sts_names():
        return sorted(s["metadata"]["name"]
                      for s in api.list("apps/v1", "statefulsets"))

    wait_for(lambda: sts_names() == ["arks-webapp-0", "arks-webapp-1"])
    app = api.get(GV, "arksapplications", "default", "webapp")
    assert FINALIZER in app["metadata"]["finalizers"]

    for n in sts_names():
        api.patch("apps/v1", "statefulsets", "default", n,
                  {"status": {"readyReplicas": 1}}, subresource="status")
    wait_for(lambda: (api.get(GV, "arksapplications", "default", "webapp")
                      .get("status", {}).get("phase")) == "Running")

    api.delete(GV, "arksapplications", "default", "webapp")
    wait_for(lambda: api.get(GV, "arksapplications", "default", "webapp") is None)
    assert sts_names() == []


def test_http_two_operators_leader_election_and_expiry_failover(tmp_path):
    """VERDICT acceptance (operator HA): TWO LiveOperators against the
    FakeApiServer over REAL HTTP — single-writer reconciliation (the
    standby ingests nothing), optimistic-concurrency Lease takeover through
    the wire's 409 mapping, and failover on lease EXPIRY when the leader
    dies without releasing."""
    from arks_tpu.control import resources as res
    from arks_tpu.control.leader import LeaderElector

    srv = FakeApiServer()
    srv.start()

    def mk(ident, lease_s):
        api = KubeApi(srv.url)
        elector = LeaderElector(api, namespace="arks-system",
                                identity=ident, lease_duration_s=lease_s,
                                retry_period_s=0.05)
        return LiveOperator(api, models_root=str(tmp_path / ident),
                            interval_s=0.1, leader_elector=elector,
                            exit_on_lost_lease=False)

    # 5s lease: long enough that suite-load starvation cannot steal
    # it mid-test, short enough that the expiry-failover phase stays
    # quick.
    a = mk("op-a", lease_s=5.0)
    b = mk("op-b", lease_s=5.0)
    client = KubeApi(srv.url)
    a.start()
    try:
        wait_for(lambda: a.is_leader)
        b.start()
        client.create(GV, "arksmodels", "default",
                      _cr("ArksModel", "m1", {"model": "org/m"}))
        client.create(GV, "arksapplications", "default", _cr(
            "ArksApplication", "app1", {
                "replicas": 1, "size": 1, "runtime": "jax",
                "model": {"name": "m1"}, "servedModelName": "served",
                "modelConfig": "tiny"}))
        wait_for(lambda: [s["metadata"]["name"] for s in client.list(
            "apps/v1", "statefulsets")] == ["arks-app1-0"])
        # Single writer: the standby's machinery never started, its store
        # is empty, and the lease names the leader.
        assert a.is_leader and not b.is_leader
        assert b.store.list(res.Application) == []
        lease = client.get("coordination.k8s.io/v1", "leases",
                           "arks-system", "e4ada7ad.arks.ai")
        assert lease["spec"]["holderIdentity"] == "op-a"

        # Crash the leader WITHOUT releasing (elector stops renewing):
        # the standby must take over only after expiry, via a
        # resourceVersion-fenced PUT over HTTP.
        a.elector.stop(release=False)
        a._stop_machinery()
        from arks_tpu.control.leader import _parse_rfc3339
        dead = client.get("coordination.k8s.io/v1", "leases",
                          "arks-system", "e4ada7ad.arks.ai")["spec"]
        expiry = (_parse_rfc3339(dead["renewTime"])
                  + dead["leaseDurationSeconds"])
        wait_for(lambda: b.is_leader, timeout=30.0)
        wait_for(lambda: b._machinery_started)
        lease = client.get("coordination.k8s.io/v1", "leases",
                           "arks-system", "e4ada7ad.arks.ai")
        assert lease["spec"]["holderIdentity"] == "op-b"
        assert int(lease["spec"]["leaseTransitions"]) >= 1
        # EXPIRY-gated takeover, proven from the Lease's own timestamps:
        # op-b acquired only after the dead leader's lease ran out.
        assert _parse_rfc3339(lease["spec"]["acquireTime"]) >= expiry

        # The new leader reconciles fresh CRs.
        client.create(GV, "arksapplications", "default", _cr(
            "ArksApplication", "app2", {
                "replicas": 1, "size": 1, "runtime": "jax",
                "model": {"name": "m1"}, "servedModelName": "served2",
                "modelConfig": "tiny"}))
        wait_for(lambda: "arks-app2-0" in [
            s["metadata"]["name"]
            for s in client.list("apps/v1", "statefulsets")])
    finally:
        b.stop()
        a.stop()
        srv.stop()
