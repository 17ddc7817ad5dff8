"""Deferred delivery (PR 30; since PR 39 by what is in flight).

A resolve that leaves NOTHING in flight on the device (a sequential one
always; a pipelined one that popped the last dispatch) keeps its frames
back (``engine._deferred``) and hands them to their readers right after
the NEXT dispatch (``phase.<phase>.deliver``), or in the next ``step()``
if that issues nothing; a resolve with a newer dispatch in flight behind
it puts every frame at once.  What the next batch needs (the token
appended, the mirrors, the stop check, the slot freed) never waits.

One engine a (depth, speculative) pair serves all cases of that pair:

- (a) a burst of more requests than slots streams, token for token and
  reason for reason, what the same requests stream one at a time;
- (b) per request the frames arrive in the order produced, ``finished``
  once and last; an abort raised while a deferral is open yields the
  deferred tokens, then the abort frame;
- (c) inside a profiler window a ``deliver`` span begins after the
  dispatch that follows the resolve it belongs to has ended, with
  callers within the slots as with a burst; the drain's last resolve
  defers and a steady depth-2 resolve does not; a request that ends
  alone is delivered by the next ``step()``; a first token's frame is
  put once;
- (d) the engine never blocks on its queue nor goes idle over a deferral,
  and a step that raises into recovery delivers what was deferred first;
- (e) ``fanout_outputs_total`` counts the frames the clients received,
  ``fanout_deferred_outputs_total`` those made with the device empty.
"""

import queue
import types

import pytest

from arks_tpu.engine import (EngineConfig, InferenceEngine, Request,
                             SamplingParams)
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

SLOTS = 2


@pytest.fixture(scope="module", params=[(0, False), (2, False),
                                        (0, True), (2, True)],
                ids=["depth0", "depth2", "depth0-spec", "depth2-spec"])
def served(request):
    depth, spec = request.param
    mp = pytest.MonkeyPatch()
    mp.setenv("ARKS_TRACE", "1")
    mp.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    mp.setenv("ARKS_MIXED_STEP", "auto")
    cfg = get_config("tiny")
    kw = dict(model="tiny", num_slots=SLOTS, max_cache_len=64,
              prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
              prefill_chunk=16, kv_layout="paged")
    if spec:
        kw.update(draft_model="tiny", draft_len=3)
    eng = InferenceEngine(cfg, EngineConfig(**kw), ByteTokenizer())
    if depth:
        assert eng._pipe_warm_wait(120) == "ready"
    try:
        yield cfg, eng, spec
    finally:
        mp.undo()


def _traffic(cfg, tag, stop_tok=None, shift=0):
    """Six requests (seven with ``stop_tok``: one that ends on a stop
    token): one- and multi-chunk prompts, greedy and seeded sampling.
    ``shift`` makes the prompts other ones, which no earlier case left in
    the prefix cache (a cached prompt prefills in one step)."""
    P, v = SamplingParams, cfg.vocab_size

    def ids(xs):
        return [(x + shift) % v for x in xs]

    reqs = [
        Request(f"{tag}-0", ids([5, 6, 7]), P(
            max_tokens=6, temperature=0.0, ignore_eos=True)),
        Request(f"{tag}-1", ids(range(3, 40)), P(
            max_tokens=5, temperature=0.0, ignore_eos=True)),
        Request(f"{tag}-2", ids([9, 8, 7, 6]), P(
            max_tokens=7, temperature=0.8, top_p=0.9, seed=7,
            ignore_eos=True)),
        Request(f"{tag}-3", ids(3 * x for x in range(1, 22)), P(
            max_tokens=4, temperature=1.0, seed=11, ignore_eos=True)),
        Request(f"{tag}-4", ids([11, 12]), P(
            max_tokens=8, temperature=0.0, ignore_eos=True)),
        Request(f"{tag}-5", ids([21, 22, 23, 24, 25]), P(
            max_tokens=3, temperature=0.7, top_k=5, seed=3,
            ignore_eos=True)),
    ]
    if stop_tok is not None:
        reqs.append(Request(f"{tag}-6", ids([5, 6, 7]), P(
            max_tokens=6, temperature=0.0, ignore_eos=True,
            stop_token_ids=[stop_tok])))
    return reqs


def _quiet(eng) -> bool:
    return (eng.num_running == 0 and eng._queue.empty()
            and not eng._prefilling)


def _drive(eng, n_steps=3000, until=None):
    """Step until the engine is idle (no deferral is open then), or until
    ``until(eng)``."""
    until = until or (lambda e: e.idle)
    for _ in range(n_steps):
        eng.step(block_s=0.01)
        if until(eng):
            return
    raise AssertionError("engine did not drain")


def _reader(req):
    """The queue the client reads (recovery wraps it in a replay gate)."""
    return getattr(req.outputs, "_inner", req.outputs)


def _frames(req):
    """Every frame in the request's queue, up to and with ``finished``;
    nothing may follow it."""
    out = []
    while True:
        f = _reader(req).get(timeout=60)
        out.append(f)
        if f.finished:
            assert _reader(req).empty(), "a frame after the finished one"
            return out


def _stream(frames):
    assert [f.finished for f in frames] == [False] * (len(frames) - 1) + [
        True]
    return [t for f in frames for t in f.token_ids], frames[-1].finish_reason


def _counts(eng):
    m = eng.metrics
    return (m.fanout_outputs_total.get(),
            m.fanout_deferred_outputs_total.get())


def _observed(hist):
    return sum(n for _, _, n in hist._data.values())


def _dispatches(eng):
    """Sequential and pipelined dispatches issued so far."""
    return (_observed(eng.metrics.mixed_batch_tokens),
            _observed(eng.metrics.pipeline_depth_occupancy))


def _solo(eng, reqs):
    out = []
    for r in reqs:
        eng.add_request(r)
        _drive(eng)
        out.append(_stream(_frames(r)))
    return out


def _frames_now(req):
    """The frames in the request's queue NOW, with no further step."""
    frames = []
    while True:
        try:
            frames.append(_reader(req).get_nowait())
        except queue.Empty:
            return frames


def _watch_resolves(eng, monkeypatch):
    """Every resolve by its kind, in order: ``seq`` (a sequential one),
    ``last`` (a pipelined one that left nothing in flight), ``steady``
    (one with a dispatch behind it); and every frame through ``_deliver``
    as (the kind of the resolve that made it, ``outside`` for none; the
    frame; whether the deferral was open).  Returns (resolves, frames)."""
    resolves, seen, where = [], [], ["outside"]

    def wrap(name, kind):
        real = getattr(eng, name)

        def inner(self, *a, **kw):
            where.append(kind(self))
            resolves.append(where[-1])
            try:
                return real(*a, **kw)
            finally:
                where.pop()
        monkeypatch.setattr(eng, name, types.MethodType(inner, eng))

    wrap("_resolve_mixed", lambda e: "seq")
    wrap("_resolve_spec_mixed", lambda e: "seq")
    wrap("_pipe_resolve_body",
         lambda e: "steady" if len(e._pipe_inflight) > 1 else "last")
    real = eng._deliver

    def deliver(self, req, out):
        real(req, out)
        seen.append((where[-1], out, self._deferred is not None))
    monkeypatch.setattr(eng, "_deliver", types.MethodType(deliver, eng))
    return resolves, seen


# --------------------------------------------- (a), (b), (e): the streams

def test_a_burst_streams_what_its_requests_stream_alone(served):
    cfg, eng, _ = served
    all0, def0 = _counts(eng)
    first = _solo(eng, _traffic(cfg, "solo"))
    stop_tok = first[0][0][2]
    solo = first + _solo(eng, _traffic(cfg, "solo2", stop_tok)[6:])
    all1, def1 = _counts(eng)
    # one caller on two slots: its sequential resolves defer all the same
    assert 0 < def1 - def0 <= all1 - all0
    assert solo[6] == (first[0][0][:2], "stop")

    reqs = _traffic(cfg, "burst", stop_tok)
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    frames = [_frames(r) for r in reqs]
    assert [_stream(f) for f in frames] == solo
    assert [r for _, r in solo] == ["length"] * 6 + ["stop"]
    all2, def2 = _counts(eng)
    # (e) the pair adds up: all = what the clients received, and some of
    # it left behind a dispatch
    assert all2 - all1 == sum(len(f) for f in frames)
    assert 0 < def2 - def1 <= all2 - all1
    assert eng._deferred is None and eng.idle


def test_an_abort_follows_the_tokens_deferred_before_it(served):
    cfg, eng, _ = served
    reqs = _traffic(cfg, "ab")
    for r in reqs:
        eng.add_request(r)
    victim = held = None
    for _ in range(3000):
        eng.step(block_s=0.01)
        open_ = eng._deferred or []
        live = {st.request.request_id for st in eng._slots.values()}
        held = [(r, o) for r, o in open_
                if r.request_id in live and o.token_ids and not o.finished]
        if held:
            victim = held[-1][0]
            break
        assert not _quiet(eng), "no deferral opened over a live stream"
    seen_before = victim.outputs.qsize()
    eng.abort(victim.request_id)
    _drive(eng)
    got = _frames(victim)
    assert got[-1].finish_reason == "abort" and got[-1].finished
    # the frames that were deferred when the abort was raised reached the
    # reader, in order, before the abort frame
    mine = [o for r, o in held if r is victim]
    assert mine and got[seen_before:seen_before + len(mine)] == mine
    assert all(not f.finished for f in got[:-1])
    for r in reqs:
        if r is not victim:
            assert _stream(_frames(r))[1] == "length"


# ------------------------------------------------- (c): where it runs

def _sections(spans):
    keep = ("dispatch", "wait", "fanout", "deliver", "issue", "resolve")
    out = [s for s in spans if s["name"].count(".") >= 2
           and s["name"].rsplit(".", 1)[1] in keep]
    return sorted(out, key=lambda s: s["start"])


def test_deliver_runs_behind_the_next_dispatch(served, tmp_path):
    cfg, eng, spec = served
    tag = "phase.spec." if spec else "phase.mixed."
    _, def0 = _counts(eng)
    assert eng.profiler.start(str(tmp_path / "p"))["ok"]
    try:
        reqs = _traffic(cfg, "win", shift=101)
        for r in reqs:
            eng.add_request(r)
        _drive(eng)
    finally:
        spans = eng.profiler.stop()["spans"]
    for r in reqs:
        assert _stream(_frames(r))[1] == "length"
    secs = _sections(spans)
    delivers = [i for i, s in enumerate(secs)
                if s["name"].endswith(".deliver")]
    assert tag + "deliver" in {secs[i]["name"] for i in delivers}
    _assert_behind_the_next_dispatch(secs, delivers, tag)
    _, def1 = _counts(eng)
    assert sum(secs[i]["arg"] for i in delivers) == def1 - def0 > 0


def _holds_back(s, tag):
    """A section in which a resolve leaves nothing in flight: a
    sequential fan-out, or a pipelined resolve with none behind it (its
    arg: the dispatches still in flight)."""
    return (s["name"] == tag + "fanout"
            or (s["name"] == "phase.decode.resolve" and s["arg"] == 0))


def _assert_behind_the_next_dispatch(secs, delivers, tag):
    for i in delivers:
        d, before = secs[i], secs[i - 1]
        assert before["end"] <= d["start"]
        if d["name"] == "phase.step.deliver":
            # a step with nothing to issue (every stream finished in the
            # resolve that held the frames back) delivers without one
            assert _holds_back(before, tag), before
            continue
        # the section just before a delivery is the dispatch it hides
        # behind: the sequential step's, or a pipelined issue ...
        assert before["name"] == {
            tag + "deliver": tag + "dispatch",
            "phase.decode.deliver": "phase.decode.issue"}[d["name"]]
        # ... and before that dispatch came the resolve that held the
        # frames back, with no other delivery in between
        held = [s for s in secs[:i - 1]
                if _holds_back(s, tag) or s["name"].endswith(".deliver")]
        assert held and _holds_back(held[-1], tag), held[-1:]


def test_callers_within_the_slots_defer_a_sequential_resolves_frames(
        served, tmp_path, monkeypatch):
    """... and they are delivered behind the next dispatch."""
    cfg, eng, spec = served
    tag = "phase.spec." if spec else "phase.mixed."
    resolves, seen = _watch_resolves(eng, monkeypatch)
    all0, def0 = _counts(eng)
    assert eng.profiler.start(str(tmp_path / "q"))["ok"]
    try:
        reqs = _traffic(cfg, "fit", shift=53)[:SLOTS]
        for r in reqs:
            eng.add_request(r)
        _drive(eng)
    finally:
        spans = eng.profiler.stop()["spans"]
    n = sum(len(_frames(r)) for r in reqs)
    all1, def1 = _counts(eng)
    assert all1 - all0 == n == len(seen)
    # the frames of the resolves that left the device empty, and no other
    made_empty = [o for kind, o, _ in seen if kind in ("seq", "last")]
    assert [o for _, o, held in seen if held] == made_empty
    assert def1 - def0 == len(made_empty) > 0
    assert "seq" in resolves
    if not eng._pipe_depth:
        assert def1 - def0 == n
    secs = _sections(spans)
    delivers = [i for i, s in enumerate(secs)
                if s["name"].endswith(".deliver")]
    assert tag + "deliver" in {secs[i]["name"] for i in delivers}
    _assert_behind_the_next_dispatch(secs, delivers, tag)
    assert sum(secs[i]["arg"] for i in delivers) == def1 - def0


def test_the_drains_last_resolve_defers_and_a_steady_one_does_not(
        served, monkeypatch):
    cfg, eng, _ = served
    resolves, seen = _watch_resolves(eng, monkeypatch)
    # Whether a pipelined resolve finds a dispatch behind it is a race of
    # the host against the device (a step resolves at once what is ready):
    # on a loaded machine every dispatch of two short streams can be done
    # before the host looks.  The traffic repeats, on other prompts, until
    # one resolve was a steady one; every check below holds of every round.
    for attempt in range(8):
        reqs = [_traffic(cfg, f"pipe{attempt}", shift=67 + attempt)[i]
                for i in (0, 4)]
        for r in reqs:
            eng.add_request(r)
        # idle, and the overshoot dispatch behind the last stream resolved
        _drive(eng, until=lambda e: e.idle and not e._pipe_inflight)
        for r in reqs:
            assert _stream(_frames(r))[1] == "length"
        if eng._pipe_depth < 2 or "steady" in resolves:
            break
    if eng._pipe_depth < 2:
        assert set(resolves) == {"seq"}
    else:
        assert {"seq", "steady", "last"} <= set(resolves)
    # a frame is held back iff its resolve left the device empty; a steady
    # resolve's frames (one dispatch still in flight) were put at once
    assert not [kind for kind, _, held in seen
                if held != (kind in ("seq", "last"))]
    steady = [o for kind, o, _ in seen if kind == "steady"]
    assert bool(steady) == (eng._pipe_depth >= 2)
    assert all(o.t_made is None and o.t_put is not None for o in steady)


def test_a_request_that_ends_alone_is_delivered_by_the_next_step(served):
    cfg, eng, _ = served
    req = _traffic(cfg, "alone", shift=83)[0]
    eng.add_request(req)
    _drive(eng, until=_quiet)
    # its last resolve is behind it; what that held back is not lost and
    # the engine is not idle over it
    got = _frames_now(req)
    held = [o for r, o in eng._deferred or [] if r is req]
    assert (got + held)[-1].finished and (held or eng.idle)
    assert eng.idle == (eng._deferred is None)
    n0 = _dispatches(eng)
    eng.step(block_s=0.01)
    # one step() that issues nothing delivers it
    assert _dispatches(eng) == n0
    got += _frames_now(req)
    assert got[len(got) - len(held):] == held
    assert _stream(got)[1] == "length"
    assert eng._deferred is None and eng.idle


def test_a_first_tokens_frame_is_put_exactly_once(served):
    cfg, eng, _ = served
    n0 = _observed(eng.metrics.time_to_first_token_seconds)
    _, def0 = _counts(eng)
    reqs = _traffic(cfg, "ttft", shift=97)
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    assert _observed(eng.metrics.time_to_first_token_seconds) - n0 == len(
        reqs)
    for r in reqs:
        frames = _frames_now(r)
        assert _stream(frames)[1] == "length"
        firsts = [f for f in frames if f.ttft_s is not None]
        # made in a sequential resolve (a prompt completes in one), kept
        # for the next dispatch, and put once
        assert len(firsts) == 1 and firsts[0] is frames[0]
        assert firsts[0].t_made is not None
        assert firsts[0].t_made <= firsts[0].t_put
        assert len({id(f) for f in frames}) == len(frames)
        assert len(frames[0].token_ids) == 1
    assert _counts(eng)[1] - def0 >= len(reqs)


# ------------------------------------- (d): never slept on, never lost

def test_the_engine_never_waits_on_its_queue_over_a_deferral(served,
                                                             monkeypatch):
    cfg, eng, _ = served
    real_get, waits = eng._queue.get, []

    def get(*a, **kw):
        # the idle wait of step(): nothing may be held back across it
        waits.append(eng._deferred)
        return real_get(*a, **kw)

    monkeypatch.setattr(eng._queue, "get", get)
    reqs = _traffic(cfg, "drain")
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    # drained: every last frame is in its queue NOW, with no further step
    for r in reqs:
        frames = []
        while True:
            try:
                frames.append(r.outputs.get_nowait())
            except queue.Empty:
                break
        assert _stream(frames)[1] == "length"
    assert eng.idle and eng._deferred is None
    # an idle wait or two (a depth-2 engine first drains its pipeline)
    assert not all(eng.step(block_s=0.01) for _ in range(3))
    assert waits and all(w is None for w in waits)


def test_the_engine_thread_drains_a_burst_and_stops_clean(served):
    cfg, eng, _ = served
    _, def0 = _counts(eng)
    reqs = _traffic(cfg, "thr")
    eng.start()
    try:
        for r in reqs:
            eng.add_request(r)
        assert [_stream(_frames(r))[1] for r in reqs] == ["length"] * 6
    finally:
        eng.stop()
        eng._thread = None
    assert eng._deferred is None
    assert _counts(eng)[1] > def0


def test_a_step_that_raises_delivers_what_was_deferred_first(served):
    cfg, eng, spec = served
    reqs = _traffic(cfg, "flt")
    for r in reqs:
        eng.add_request(r)
    held = None
    for _ in range(3000):
        eng.step(block_s=0.01)
        if eng._deferred:
            held = list(eng._deferred)
            break
        assert not _quiet(eng), "no deferral opened"
    phase = "spec" if spec else "decode"
    nth = eng._faults._counts.get(phase, 0) + 1
    eng._faults.arm(f"{phase}:{nth}:runtime")
    with pytest.raises(Exception) as err:
        eng.step(block_s=0.01)
    # nothing left the list on the way to the fault, nothing was lost
    assert eng._deferred is not None and eng._deferred[:len(held)] == held
    eng._recover_from_fault(err.value)
    assert eng._deferred is None
    for r, o in held:
        q_ = _reader(r).queue
        assert any(f is o for f in q_), o
        at = next(i for i, f in enumerate(q_) if f is o)
        assert not [f for f in list(q_)[:at] if f.finish_reason == "error"]
    for _ in range(3000):
        try:
            eng.step(block_s=0.01)
        except Exception as e:  # noqa: BLE001 — routed as _run_loop does
            eng._recover_from_fault(e)
        if eng.idle and eng.state == "serving":
            break
    for r in reqs:
        assert _stream(_frames(r))[1] in ("length", "error")
    assert eng._deferred is None
