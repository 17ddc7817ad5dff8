"""One family, every block that steps ahead of the host (ROADMAP D9): the
engine at pipeline depth 2 serves the streams of the engine at depth 0.
A block joins by its preset's name."""

import pytest

import harness


@pytest.mark.parametrize("preset", [
    "tiny-linear-moe", "tiny-latent-linear-moe", "tiny-swa-moe"])
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
    harness.streams_agree(got, want)
