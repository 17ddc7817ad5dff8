"""One family, every block that steps ahead of the host (ROADMAP D9): the
engine at pipeline depth 2 serves the streams of the engine at depth 0.
A block joins by its preset's name."""

import pytest

import harness


@pytest.mark.parametrize("preset", [
    "tiny-linear-moe", "tiny-latent-linear-moe", "tiny-swa-moe",
    "tiny-shortcut-mla-moe", "tiny-ssm-moe"])
def test_the_pipelined_path_gives_the_sequential_streams(preset, monkeypatch):
    """Depth 2 runs a step ahead of the host's lengths: a lane the device
    found dead takes no recurrence step, the window pages are covered from
    the resolved length for every dispatch in flight, and the streams are
    the sequential path's."""
    with harness.fresh(preset) as eng:
        assert eng.resolved_config["pipeline_depth"] == "0"
        want = harness.serve(eng)
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", "2")
    with harness.fresh(preset) as eng:
        assert eng.resolved_config["pipeline_depth"] == "2"
        got = harness.serve(eng)
        assert eng.metrics.pipeline_depth_occupancy._data
        if eng.cfg.zero_experts:
            _counts_add_up(eng)
    harness.streams_agree(got, want)


def _counts_add_up(eng):
    """A block whose routers score identity experts, held whole: every pair
    lands on a held expert or on an identity one (``routed = held + zero``,
    nothing absent), a third or so on the latter; the pool took a row a
    token an attention SUBLAYER, sequential and pipelined steps alike."""
    m, cfg = eng.metrics, eng.cfg

    routed, held, zero = (c.total() for c in (
        m.moe_routed_pairs_total, m.moe_held_pairs_total,
        m.moe_zero_pairs_total))
    assert routed == held + zero and 0.2 < zero / routed < 0.5
    rows = routed // (cfg.num_experts_per_tok * cfg.num_routed_layers)
    assert m.mixed_latent_rows_total.total() == rows * 4
    assert eng._cache.k.shape[0] == 4 and eng._cache.token_bytes == 320
    assert eng._page_bytes == 16 * 320
