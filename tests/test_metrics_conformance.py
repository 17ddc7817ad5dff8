"""Prometheus exposition conformance: label-value escaping and a
registry-wide metric-name census (naming conventions + no duplicate
families across the engine, gateway, router, and operator registries)."""

import re

import pytest

from arks_tpu.utils import metrics as prom
from arks_tpu.utils.metrics import _fmt_labels


# ---------------------------------------------------------------- escaping

def test_label_value_backslash_escaped():
    assert _fmt_labels({"path": r"C:\tmp"}) == '{path="C:\\\\tmp"}'


def test_label_value_quote_escaped():
    assert _fmt_labels({"q": 'say "hi"'}) == '{q="say \\"hi\\""}'


def test_label_value_newline_escaped():
    assert _fmt_labels({"m": "a\nb"}) == '{m="a\\nb"}'


def test_label_value_backslash_before_quote_order():
    # \" in the raw value must become \\\" (escape the backslash first,
    # then the quote) — not \\" which would terminate the value early.
    assert _fmt_labels({"v": '\\"'}) == '{v="\\\\\\""}'


def test_escaped_render_is_parseable():
    """A scrape line with hostile label values must round-trip under the
    Prometheus text-format grammar (no raw newline, balanced quotes)."""
    reg = prom.Registry()
    c = reg.counter("hostile_values_total", "escaping probe")
    c.inc(user='a"b', path="c\\d", note="e\nf")
    text = reg.render()
    sample_lines = [ln for ln in text.splitlines()
                    if ln.startswith("hostile_values_total{")]
    assert len(sample_lines) == 1
    line = sample_lines[0]
    assert "\n" not in line
    # Every quote inside the label braces is either a delimiter or escaped.
    body = line[line.index("{") + 1:line.rindex("}")]
    # Unescape per exposition-format rules and check the originals survive.
    m = dict(re.findall(r'(\w+)="((?:\\.|[^"\\])*)"', body))
    unesc = {k: v.replace("\\n", "\n").replace('\\"', '"')
                  .replace("\\\\", "\\") for k, v in m.items()}
    assert unesc == {"user": 'a"b', "path": "c\\d", "note": "e\nf"}


def test_histogram_le_labels_still_render():
    reg = prom.Registry()
    h = reg.histogram("probe_seconds", "h", buckets=[0.1, 1.0])
    h.observe(0.05, op='x"y')
    text = reg.render()
    assert 'le="0.1"' in text and 'op="x\\"y"' in text


# ------------------------------------------------------------- duplicates

def test_duplicate_family_rejected():
    reg = prom.Registry()
    reg.counter("dup_total", "first")
    with pytest.raises(ValueError):
        reg.counter("dup_total", "second")
    with pytest.raises(ValueError):
        reg.gauge("dup_total", "different type, same family")


# ----------------------------------------------------------------- census
#
# The name census (snake_case, _total discipline, no duplicate families
# across components) is now the arkslint ``metrics`` rule — a STATIC
# walk of every registration call, so it covers registries the runtime
# construction below might never instantiate.  These wrappers keep the
# test names; the runtime cross-check at the bottom asserts the live
# registries still agree with what the static census saw.


def _metric_errors(*checks):
    from arks_tpu.analysis import SourceTree, repo_root, run_rules
    findings = run_rules(SourceTree.load(repo_root()), ["metrics"])
    return [f.render() for f in findings
            if f.severity == "error" and f.check in checks]


def test_census_snake_case_and_counter_suffix():
    assert not _metric_errors("name-convention"), (
        _metric_errors("name-convention"))


def test_census_no_family_registered_twice_across_components():
    assert not _metric_errors("duplicate-family"), (
        _metric_errors("duplicate-family"))


def test_tenant_label_cardinality_bounded():
    """Per-tenant metric families must not explode under hostile tenant
    churn: a thousand distinct tenants through the TenantLabels bound
    land on at most cap distinct labels plus the shared "other" bucket,
    and nothing is lost — the counter total still sees every event."""
    from arks_tpu import tenancy
    reg = prom.Registry()
    shed = reg.counter("cardinality_probe_total", "bounded-label probe")
    labels = tenancy.TenantLabels(cap=32)
    for i in range(1000):
        shed.inc(tenant=labels.label(f"churn/user{i}"))
    seen = {dict(k)["tenant"] for k in shed._values}
    assert len(seen) <= 32 + 1
    assert tenancy.OTHER_LABEL in seen
    assert shed.get(tenant=tenancy.OTHER_LABEL) == 1000 - 32
    assert shed.total() == 1000


def test_census_matches_live_registries():
    """The static census must actually see the real registries: every
    family the live engine/gateway/router registries expose appears in
    the static registration walk, and the walk saw a census-sized set."""
    from arks_tpu.analysis import SourceTree, repo_root
    from arks_tpu.analysis.rules import metrics as metrics_rule
    from arks_tpu.engine.engine import EngineMetrics
    from arks_tpu.gateway.metrics import GatewayMetrics, RouterMetrics

    static = {name for _path, _scope, _kind, name, _line
              in metrics_rule.registrations(SourceTree.load(repo_root()))
              if name}
    live = set()
    for reg in (EngineMetrics().registry, GatewayMetrics().registry,
                RouterMetrics().registry):
        live |= {fam.name for fam in reg.families()}
    missing = live - static
    assert not missing, f"live families invisible to the census: {missing}"
    assert len(static) > 40  # the census actually saw the real registries


def test_fanout_counter_pair_renders_beside_each_other():
    """Deferred delivery's pair (PR 30): every frame handed to a reader,
    and those of them that left behind the next dispatch; both unlabeled
    counters, both on the engine's /metrics from the first scrape on (a
    share needs its denominator even while it reads 0)."""
    from arks_tpu.engine.engine import EngineMetrics
    m = EngineMetrics()
    kinds = {fam.name: fam.type for fam in m.registry.families()}
    assert kinds["fanout_outputs_total"] == "counter"
    assert kinds["fanout_deferred_outputs_total"] == "counter"

    def rendered():
        return {line.rpartition(" ")[0]: float(line.rpartition(" ")[2])
                for line in m.registry.render().splitlines()
                if line.startswith("fanout_")}

    assert rendered() == {"fanout_outputs_total": 0.0,
                          "fanout_deferred_outputs_total": 0.0}
    m.fanout_outputs_total.inc(5)
    m.fanout_deferred_outputs_total.inc(2)
    assert rendered() == {"fanout_outputs_total": 5.0,
                          "fanout_deferred_outputs_total": 2.0}



def test_step_clock_families_and_their_zero_reading():
    """The step clock's families (PR 38): counters end in ``_total``, the
    cycle and lag families are histograms, the resolve wait has no second
    family, and both stall families read 0 for every ``where`` from the
    first scrape (a sound run's ``step_stall_s`` is 0.0, not nothing)."""
    from arks_tpu.engine.engine import EngineMetrics
    from arks_tpu.obs import stepclock
    m = EngineMetrics()
    kinds = {fam.name: fam.type for fam in m.registry.families()}
    assert {n: kinds[n] for n in kinds if n.startswith(
        ("step_leg", "step_call", "step_cycle", "step_stall", "host_wake",
         "stream_"))} == {
        "step_leg_seconds_total": "counter",
        "step_call_seconds_total": "counter",
        "step_cycle_seconds": "histogram",
        "step_stalls_total": "counter",
        "step_stall_seconds_total": "counter",
        "host_wake_late_seconds": "histogram",
        "stream_deliver_lag_seconds": "histogram",
        "stream_defer_lag_seconds": "histogram"}
    assert "decode_resolve_wait_seconds_total" not in kinds
    lines = m.registry.render().splitlines()
    for family in ("step_stalls_total", "step_stall_seconds_total"):
        assert sorted(ln for ln in lines if ln.startswith(family + "{")) \
            == sorted(f'{family}{{where="{w}"}} 0' for w in stepclock.WHERE)
    assert m.step_cycle_seconds.buckets[0] == 0.005
    assert m.step_cycle_seconds.buckets[-1] == 2.0
