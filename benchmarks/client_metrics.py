"""From the load generator's per-request records to what a client saw.

The yardstick for every client-side number; later PRs cannot change it.

- A content frame carries ``n`` characters of text.  Under the byte
  tokenizer a token is a byte, and the server's incremental UTF-8 decoder
  holds a lead byte back until the sequence completes or breaks, so a frame
  may carry the text of two tokens and a token's text may arrive one step
  late.  Tokens inside the window are therefore counted as characters
  inside the window times ``tokens_per_char``, the ratio of completion
  tokens (from the usage frame) to characters over every finished stream
  of the run (about 1.04 on random bytes); a gap before a frame of ``n``
  characters counts as ``n`` gaps of ``gap / n``.
- ``output_tok_s.burst`` is ``output_tok_s``, the same count over the same
  window, under the name it is judged by in a cell whose window is a slice
  of one burst's admission: there the streams live grow all through the
  window, so the number follows how many streams the pod has admitted by
  then, and that count swings from run to run at one step time (100 to 105
  of 240, PERF.md §6): it takes a wider bound than a sustained rate.  A
  cell lists one of the two names.
- Time to first token runs from when the request was DUE (open loop: the
  schedule; closed loop: when it was sent) to its first content frame.
- ``attempted``, open loop: the requests due inside the window.  One that
  errors, is shed, or has no first token by the end of the drain counts in
  ``failed`` and enters the percentiles with the time it had waited by then.
- ``attempted``, closed loop: every request the window saw (sent before it
  closed, not finished before it opened).  Callers outnumber what the pod
  admits at once by design, so a request still queued for its first token
  has not failed (the server answers with its status line only when the
  first token is there); one that errors, is shed or ends wrong has.
"""

from __future__ import annotations


def percentile(values: list[float], q: float) -> float | None:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def stream_ok(rec: dict) -> bool:
    """A finished stream had the token counts and finish reason asked for."""
    u = rec.get("usage") or {}
    return (rec["finish"] == "length"
            and u.get("completion_tokens") == rec["max_tokens"]
            and u.get("prompt_tokens") == rec["prompt_tokens"])


def reduce(run: dict, chips: int = 1) -> dict:
    t_open, t_close, t_end = run["t_open"], run["t_close"], run["t_end"]
    window = t_close - t_open
    recs = run["records"]
    due_in = [r for r in recs if t_open <= r["due"] < t_close]
    finished = [r for r in recs if r["done"]]
    wrong = [r["id"] for r in finished if not stream_ok(r)]

    def end(r):
        return r["frames"][-1][0] if r["done"] and r["frames"] else None

    if run.get("loop") == "closed":
        attempted = [r for r in recs if r["sent"] < t_close
                     and not (r["done"] and (end(r) or t_end) < t_open)]
        failed = [r for r in attempted
                  if r["status"] not in (None, 200) or r["error"]
                  or (r["done"] and not stream_ok(r))]
    else:
        attempted = due_in
        failed = [r for r in due_in
                  if r["status"] != 200 or r["error"] or r["first"] is None]
    chars_done = sum(n for r in finished for _, n in r["frames"])
    toks_done = sum((r["usage"] or {}).get("completion_tokens", 0)
                    for r in finished)
    # No stream finished (a hopeless overload): count a character a token.
    tokens_per_char = toks_done / chars_done if chars_done else 1.0
    chars_in = 0
    gaps: list[float] = []
    for r in recs:
        prev = None
        for t, n in r["frames"]:
            if t_open <= t < t_close:
                chars_in += n
                if prev is not None:
                    gaps.extend([(t - prev) / n] * n)
            prev = t
    ttft = [((r["first"] if r["first"] is not None else t_end) - r["due"])
            for r in due_in]
    late = [r["sent"] - r["due"] for r in due_in]
    tok_s = chars_in * tokens_per_char / window / chips
    out = {
        "window_s": window,
        "attempted": len(attempted),
        "failed": len(failed),
        "due_in_window": len(due_in),
        "first_in_window": sum(1 for r in recs if r["first"] is not None
                               and t_open <= r["first"] < t_close),
        "finished_in_window": sum(1 for r in finished
                                  if t_open <= (end(r) or 0) < t_close),
        "waiting_first_at_open": sum(
            1 for r in recs if r["sent"] < t_open
            and (r["first"] is None or r["first"] >= t_open)),
        "waiting_first_at_close": sum(
            1 for r in recs if r["sent"] < t_close
            and (r["first"] is None or r["first"] >= t_close)),
        "streaming_at_close": sum(
            1 for r in recs if r["first"] is not None
            and r["first"] < t_close and (end(r) or t_end) >= t_close),
        "failed_ids": [r["id"] for r in failed][:8],
        "finished_streams": len(finished),
        "wrong_streams": wrong[:8],
        "n_wrong_streams": len(wrong),
        "tokens_per_char": tokens_per_char,
        "chars_in_window": chars_in,
        "itl_samples": len(gaps),
        "ttft_samples": len(ttft),
        "output_tok_s": tok_s,
        "output_tok_s.burst": tok_s,
        "itl_p50_ms": _ms(percentile(gaps, 50)),
        "itl_p95_ms": _ms(percentile(gaps, 95)),
        "ttft_p50_ms": _ms(percentile(ttft, 50)),
        "ttft_p95_ms": _ms(percentile(ttft, 95)),
        "gen_late_p95_ms": _ms(percentile(late, 95)),
        "prompt_tokens_due": sum(r["prompt_tokens"] for r in due_in),
    }
    return out


def _ms(x: float | None) -> float | None:
    return None if x is None else x * 1e3
