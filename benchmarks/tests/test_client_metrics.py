import pytest

from benchmarks import client_metrics as cm


def _rec(i, due, first, frames, done=True, max_tokens=4, status=200):
    return {"id": f"r{i}", "due": due, "sent": due + 0.001, "status": status,
            "first": first, "frames": frames, "finish": "length" if done
            else None, "usage": {"completion_tokens": max_tokens,
                                 "prompt_tokens": 10} if done else None,
            "done": done, "error": None, "max_tokens": max_tokens,
            "prompt_tokens": 10}


def test_window_tokens_gaps_and_failures():
    run = {"loop": "open", "t_open": 10.0, "t_close": 20.0, "t_end": 22.0,
           "records": [
        # before the window: counts for tokens_per_char only
        _rec(0, 5.0, 5.5, [(5.5, 1), (5.6, 1), (5.7, 2)]),
        # inside: 4 tokens as 3 frames, the last carrying two characters
        _rec(1, 11.0, 11.2, [(11.2, 1), (11.3, 1), (11.5, 2)]),
        # due inside, no first token by the end of the drain: failed
        _rec(2, 19.0, None, [], done=False),
        # shed
        _rec(3, 12.0, None, [], done=False, status=503),
        # straddles the close: only frames inside count
        _rec(4, 19.5, 19.8, [(19.8, 1), (20.1, 1)], done=False),
    ]}
    out = cm.reduce(run)
    assert out["attempted"] == 4 and out["failed"] == 2
    assert out["tokens_per_char"] == 1.0
    assert out["chars_in_window"] == 5
    assert out["output_tok_s"] == pytest.approx(0.5)
    assert out["output_tok_s.burst"] == out["output_tok_s"]
    # gaps: 0.1, then 0.2 before a two-character frame = two gaps of 0.1
    assert out["itl_samples"] == 3
    assert out["itl_p95_ms"] == pytest.approx(100.0)
    # ttft: 0.2, 0.3, and the two failures at t_end - due = 3.0 and 10.0
    assert out["ttft_p50_ms"] == pytest.approx(1650.0)
    assert out["n_wrong_streams"] == 0
    # the same records as a closed loop: everything the window saw is
    # attempted (r0 finished before it opened); only the shed one failed
    closed = cm.reduce(dict(run, loop="closed"))
    assert closed["attempted"] == 4 and closed["failed"] == 1
    assert closed["waiting_first_at_close"] == 2
    assert closed["streaming_at_close"] == 1


def test_a_stream_with_the_wrong_count_is_named():
    r = _rec(0, 11.0, 11.1, [(11.1, 1)], max_tokens=4)
    r["usage"]["completion_tokens"] = 3
    out = cm.reduce({"loop": "open", "t_open": 10.0, "t_close": 20.0, "t_end": 21.0,
                     "records": [r]})
    assert out["wrong_streams"] == ["r0"]


def test_percentile_matches_numpy():
    import numpy as np
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6]
    for q in (5, 50, 95):
        assert cm.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
