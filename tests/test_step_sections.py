"""Step-section spans, the profiler window that switches them, the clock
anchors, named programs and the compilation counters (PR 24).

- section spans (``phase.<phase>.<section>``) are recorded only between
  ``ProfilerWindows.start()`` and ``stop()``; outside a window a section
  site writes nothing into the rings;
- a section folds inside its scheduler phase, and ``stop()`` hands back
  every engine-scope span of the window, however long the window was;
- the clock anchor round-trips: its name gives back the offset between
  the trace's clock and ``time.monotonic``;
- every jitted program of an engine carries a name that starts ``arks_``;
- ``xla_compilations_total`` counts a fresh shape once and a cache hit
  never;
- token streams are byte-identical with a window open and closed;
- a window's profile holds Python frames only where they were asked for,
  and a quiet window leaves its spans beside the profile, on the profile's
  clock (PR 52).
"""

import functools
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from arks_tpu.engine import InferenceEngine, Request, SamplingParams
from arks_tpu.engine.engine import _named_jit
from arks_tpu.obs import profiler as prof_mod
from arks_tpu.obs.trace import Tracer

import harness

# ``deliver`` (PR 30): the workload below puts three requests on two
# slots, so its resolves find a request waiting for a slot and hand their
# frames out behind the next dispatch.
SECTIONS = ("retire", "pack", "count", "dispatch", "deliver", "wait",
            "fanout", "promote")


def _mk_engine(monkeypatch, *, depth=0, spec=False, **kw):
    monkeypatch.setenv("ARKS_TRACE", "1")
    monkeypatch.setenv("ARKS_PIPELINE_DEPTH", str(depth))
    monkeypatch.setenv("ARKS_MIXED_STEP", "auto")
    defaults = dict(num_slots=2, max_cache_len=64,
                    prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                    prefill_chunk=16, kv_layout="paged")
    if spec:
        defaults.update(draft_model="tiny", draft_len=3)
    eng = harness.warmed("tiny", base=defaults, **kw)
    return eng.cfg, eng


_drive = harness.drive


def _collect(req):
    ids = []
    while True:
        out = req.outputs.get(timeout=120)
        ids.extend(out.token_ids)
        if out.finished:
            return ids, out.finish_reason


def _workload(eng, cfg, tag, shift=0):
    """Three requests of 3, 37 and 4 prompt tokens; ``shift`` makes the
    prompts other ones (no prefix-cache hit on an earlier run's pages)."""
    reqs = [
        Request(f"{tag}-g", [5 + shift, 6, 7], SamplingParams(
            max_tokens=6, temperature=0.0, ignore_eos=True)),
        Request(f"{tag}-l", [int(x + shift) % cfg.vocab_size
                             for x in range(3, 40)],
                SamplingParams(max_tokens=6, temperature=0.0,
                               ignore_eos=True)),
        Request(f"{tag}-s", [9 + shift, 8, 7, 6], SamplingParams(
            max_tokens=6, temperature=0.8, top_p=0.9, seed=7,
            ignore_eos=True)),
    ]
    for r in reqs:
        eng.add_request(r)
    _drive(eng)
    return [_collect(r) for r in reqs]


def _is_section(name: str) -> bool:
    return name == "phase.admit" or name.count(".") >= 2


# ------------------------------------------------------------ the sections

def test_sections_only_inside_a_window_and_nothing_written_outside(
        monkeypatch, tmp_path):
    cfg, eng = _mk_engine(monkeypatch)
    _workload(eng, cfg, "warm")
    eng.trace.flush()
    assert not eng.profiler.sections
    # Outside a window: not one section event in any ring, and the rings
    # hold exactly the events they held before the sections existed.
    outside = eng.trace.tail(n=10**6)
    assert outside and not [e for e in outside if _is_section(e["name"])]
    assert {"phase.mixed", "queue", "prefill", "finish"} <= {
        e["name"] for e in outside}

    started = eng.profiler.start(str(tmp_path / "p"))
    assert started["ok"] and eng.profiler.sections
    assert abs(started["t0_monotonic"] - time.monotonic()) < 60
    _workload(eng, cfg, "in")
    stopped = eng.profiler.stop()
    assert stopped["ok"] and not eng.profiler.sections
    names = {s["name"] for s in stopped["spans"]}
    assert {"phase.mixed." + s for s in SECTIONS} <= names
    assert {"phase.admit", "phase.step.head", "phase.step.tail"} <= names
    assert stopped["t0_monotonic"] <= min(
        s["end"] for s in stopped["spans"])
    assert eng.profiler.last_window["spans"] == stopped["spans"]

    # After the window: silent again.
    mark = time.monotonic()
    _workload(eng, cfg, "after")
    late = [e for e in eng.trace.tail(n=10**6) if e["t"] > mark]
    assert late and not [e for e in late if _is_section(e["name"])]


def test_sections_fold_inside_their_phase_and_carry_their_counts(
        monkeypatch, tmp_path):
    cfg, eng = _mk_engine(monkeypatch)
    _workload(eng, cfg, "warm")
    eng.profiler.start(str(tmp_path / "p"))
    _workload(eng, cfg, "in", shift=50)
    spans = eng.profiler.stop()["spans"]
    outer = [s for s in spans if s["name"] == "phase.mixed"]
    inner = [s for s in spans if s["name"].startswith("phase.mixed.")]
    assert outer and inner
    for s in inner:
        assert any(o["start"] <= s["start"] and s["end"] <= o["end"]
                   for o in outer), s
    # An inner span is folded before the phase that holds it: a reader
    # that takes the first span over an instant names the innermost.
    order = [s["name"] for s in spans]
    assert order.index("phase.mixed.dispatch") < len(order) - 1
    first_outer = next(i for i, s in enumerate(spans)
                       if s["name"] == "phase.mixed")
    assert any(s["name"].startswith("phase.mixed.")
               for s in spans[:first_outer])
    packs = [s["arg"] for s in spans if s["name"] == "phase.mixed.pack"]
    assert all(len(a) == 3 and a[0] >= a[1] for a in packs)
    assert sum(a[1] for a in packs) == 3 + 37 + 4      # every prompt token
    disp = {s["arg"] for s in spans if s["name"] == "phase.mixed.dispatch"}
    assert disp == {"arks_mixed_seq"}
    assert sum(s["arg"] for s in spans
               if s["name"] == "phase.mixed.promote") == 3
    assert sum(s["arg"] for s in spans if s["name"] == "phase.admit") == 3


@pytest.mark.parametrize("spec", [False, True])
def test_pipelined_and_spec_steps_have_their_sections(monkeypatch, tmp_path,
                                                      spec):
    cfg, eng = _mk_engine(monkeypatch, depth=2, spec=spec)
    _workload(eng, cfg, "warm")
    eng.profiler.start(str(tmp_path / "p"))
    # other prompts than the warm-up's: a prompt found in the prefix cache
    # prefills in one step, the step after it is pipelined, and what its
    # resolve held back leaves as ``phase.decode.deliver``
    _workload(eng, cfg, "in", shift=50)
    names = {s["name"] for s in eng.profiler.stop()["spans"]}
    assert {"phase.decode.issue", "phase.decode.resolve"} <= names
    tag = "phase.spec." if spec else "phase.mixed."
    assert {tag + s for s in SECTIONS} <= names


def test_the_run_loop_wraps_each_step_in_a_span_of_its_own(monkeypatch,
                                                          tmp_path):
    """Under the engine's own thread a traced step is one
    ``phase.step.loop`` span that ends after step() has returned: time
    between two sections (a wait for the GIL) still has a name."""
    cfg, eng = _mk_engine(monkeypatch)
    eng.start()
    try:
        assert eng.profiler.start(str(tmp_path / "p"))["ok"]
        req = Request("y-0", [5, 6, 7, 8], SamplingParams(
            max_tokens=8, temperature=0.0, ignore_eos=True))
        eng.add_request(req)
        assert _collect(req)[1] == "length"
        spans = eng.profiler.stop()["spans"]
    finally:
        eng.stop()
    loops = [sp for sp in spans if sp["name"] == "phase.step.loop"]
    inner = [sp for sp in spans if sp["name"] in (
        "phase.step.head", "phase.step.tail", "phase.mixed.dispatch",
        "phase.mixed.fanout", "phase.admit")]
    assert loops and len(inner) >= 5
    # Leave aside the step the window opened in (the loop had looked at the
    # profiler before, the step looked after) and the one it closed in (its
    # loop span has no end yet): every section from the first whole loop
    # span to the last lies inside one of them.
    first, last = loops[0]["start"], loops[-1]["end"]
    between = [sp for sp in inner
               if first <= sp["start"] and sp["end"] <= last]
    held = [sp for sp in between
            if any(lo["start"] <= sp["start"] and sp["end"] <= lo["end"]
                   for lo in loops)]
    assert held == between and len(held) >= 5, (held, inner)
    # folded after what it holds, so a reader names the inner span first
    names = [sp["name"] for sp in spans]
    assert names.index("phase.step.head") < names.index("phase.step.loop")


def test_stop_returns_every_span_of_a_long_window(monkeypatch, tmp_path):
    monkeypatch.setenv("ARKS_TRACE", "1")
    tracer = Tracer()            # no collector thread: folded by stop()
    prof = prof_mod.ProfilerWindows(str(tmp_path), tracer=tracer)
    tracer.evt("", "phase.mixed.before", "B")
    tracer.evt("", "phase.mixed.before", "E")
    assert prof.start(str(tmp_path / "p"))["ok"]
    n = 3000                     # more than the 2048 the export deque keeps
    for i in range(n):
        tracer.evt("", "phase.mixed.pack", "B")
        tracer.evt("", "phase.mixed.pack", "E", i)
    out = prof.stop()
    assert out["ok"]
    got = [s["arg"] for s in out["spans"]
           if s["name"] == "phase.mixed.pack"]
    assert got == list(range(n))
    assert not [s for s in out["spans"] if s["name"].endswith("before")]
    assert len(tracer.phase_spans()) == 2048      # the ring still is a ring
    # a second window starts empty
    assert prof.start(str(tmp_path / "q"))["ok"]
    assert prof.stop()["spans"] == []
    assert prof.stop() == {"ok": False, "error": "not_active"}


def test_an_orphaned_begin_does_not_mispair_later_sections(monkeypatch):
    monkeypatch.setenv("ARKS_TRACE", "1")
    tracer = Tracer()
    tracer.open_window()
    tracer.evt("", "phase.admit", "B")         # a fault: no end follows
    tracer.evt("", "phase.admit", "B")
    t_mid = time.monotonic()
    tracer.evt("", "phase.admit", "E", 2)
    spans = tracer.close_window()
    assert [s["arg"] for s in spans] == [2]
    assert spans[0]["start"] <= t_mid <= spans[0]["end"]
    assert spans[0]["end"] - spans[0]["start"] < 1.0


# --------------------------------------------------------------- the clock

def test_clock_anchor_round_trips():
    ns = 1532942777607
    name = prof_mod.anchor_name(ns)
    assert name == "arks_clock[1532942777607]"
    assert prof_mod.anchor_offset_s(name, 0.000130761) == pytest.approx(
        0.000130761 - 1532.942777607, abs=1e-9)
    for bad in ("arks_step[r1]", "arks_clock[]", "arks_clock[x]",
                "arks_clock[12"):
        assert prof_mod.anchor_offset_s(bad, 0.0) is None


def test_a_window_writes_two_anchors_that_join_the_clocks(monkeypatch,
                                                          tmp_path):
    from jax.profiler import ProfileData
    monkeypatch.setenv("ARKS_TRACE", "1")
    prof = prof_mod.ProfilerWindows(str(tmp_path), tracer=Tracer())
    started = prof.start(str(tmp_path / "p"))
    with jax.profiler.TraceAnnotation("arks_step[probe]"):
        t_probe = time.monotonic()
        jnp.ones((4,)).block_until_ready()
    time.sleep(0.05)
    stopped = prof.stop()
    path = glob.glob(str(tmp_path / "p" / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    offsets, probe = [], None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                off = prof_mod.anchor_offset_s(e.name, e.start_ns * 1e-9)
                if off is not None:
                    offsets.append(off)
                elif e.name == "arks_step[probe]":
                    probe = e.start_ns * 1e-9
    assert len(offsets) == 2 and probe is not None
    assert abs(offsets[0] - offsets[1]) < 1e-3          # no drift to speak of
    # the anchors lay time.monotonic on the trace: the probe annotation
    # sits where its own monotonic reading says
    assert probe == pytest.approx(t_probe + offsets[0], abs=5e-3)
    assert started["t0_monotonic"] <= t_probe <= stopped["t1_monotonic"]


@pytest.mark.parametrize("python", [False, True],
                         ids=["quiet", "python-frames"])
def test_a_profile_has_python_frames_only_where_they_were_asked_for(
        monkeypatch, tmp_path, python):
    """The default window's profile holds no event of the Python tracer
    (``$file:line function``) and keeps the host tracer's (the annotations);
    its spans lie beside it as a Chrome trace file, moved onto the
    profile's clock by the opening anchor.  ``python=True`` has the frames
    and no such file (they are its host detail)."""
    from jax.profiler import ProfileData
    monkeypatch.setenv("ARKS_TRACE", "1")
    tracer = Tracer()
    prof = prof_mod.ProfilerWindows(str(tmp_path), tracer=tracer)
    d = str(tmp_path / "p")
    assert prof.start(d, python=python)["python"] is python
    tracer.evt("", "phase.mixed.pack", "B")
    t_probe = time.monotonic()
    with jax.profiler.TraceAnnotation("arks_step[probe]"):
        sum(len(str(i)) for i in range(200))        # Python calls to hook
        jnp.ones((4,)).block_until_ready()
    tracer.evt("", "phase.mixed.pack", "E", [3, 2, 1])
    tracer.evt("", "phase.decode.issue", "B")       # still open at stop()
    out = prof.stop()
    assert out["ok"] and out["python"] is python
    path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    frames, probe, offsets = 0, None, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                frames += e.name.startswith("$")
                off = prof_mod.anchor_offset_s(e.name, e.start_ns * 1e-9)
                if off is not None:
                    offsets.append((e.start_ns, off))
                elif e.name == "arks_step[probe]":
                    probe = e.start_ns * 1e-9
    assert len(offsets) == 2 and probe is not None
    spans_file = os.path.join(d, prof_mod.SPANS_FILE)
    if python:
        assert frames > 0 and not os.path.exists(spans_file)
        return
    assert frames == 0
    with open(spans_file) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in events] == ["phase.mixed.pack"]
    (pack,) = events
    assert pack["args"] == {"arg": [3, 2, 1]}
    (span,) = out["spans"]                  # ... which stay on time.monotonic
    assert span["start"] <= t_probe <= span["end"]
    # On the profile's clock: where the span's own reading lies by the
    # opening anchor, and so beside the probe annotation (the anchor is
    # good to the microseconds between its reading and its own begin).
    assert pack["ts"] * 1e-6 == pytest.approx(
        span["start"] + min(offsets)[1], abs=1e-6)
    assert pack["dur"] * 1e-6 == pytest.approx(span["end"] - span["start"],
                                               abs=1e-6)
    assert abs(probe - pack["ts"] * 1e-6) < 5e-3


# ------------------------------------------------------ names and counters

@pytest.mark.parametrize("spec", [False, True])
def test_every_jitted_program_has_a_name_of_its_own(monkeypatch, spec):
    _, eng = _mk_engine(monkeypatch, spec=spec)
    variants = eng.compiled_program_variants()
    assert len(variants) >= 20
    names = {attr: getattr(eng, attr).__name__ for attr in variants}
    assert all(n.startswith("arks_") for n in names.values()), names
    assert len(set(names.values())) == len(names)       # none shared
    assert names["_mixed_fn"] == "arks_mixed_seq"
    assert names["_mixed_lp_fn"] == "arks_mixed_seq_lp"
    assert names["_mixed_pipe_fn"] == "arks_mixed_pipe"
    assert names["_mixed_pipe_lp_fn"] == "arks_mixed_pipe_lp"
    assert names["_chunk_fn"] == "arks_chunk"
    if spec:
        assert names["_spec_mixed_fn"] == "arks_spec_mixed"


def test_the_name_reaches_the_compiled_module_and_the_scopes_its_ops():
    def prog(x, want_lp):
        with jax.named_scope("arks.ffn"):
            y = x * 2.0
        return (y, y) if want_lp else y

    import functools
    fn = _named_jit("arks_probe", functools.partial(prog, want_lp=False))
    assert fn.__name__ == "arks_probe"
    lowered = fn.lower(jnp.ones((3,)))
    assert "jit_arks_probe" in lowered.as_text()
    assert "arks.ffn" in lowered.as_text(debug_info=True)
    # two wrappers of one callable do not share a trace cache
    a, b = _named_jit("arks_a", prog, static_argnums=1), _named_jit(
        "arks_b", prog, static_argnums=1)
    a(jnp.ones((3,)), False)
    assert a._cache_size() == 1 and b._cache_size() == 0


def test_step_scopes_are_on_the_mixed_step(monkeypatch):
    cfg, eng = _mk_engine(monkeypatch)
    _workload(eng, cfg, "w")
    # the traced program of the sequential mixed step names its parts
    from arks_tpu.models import transformer as tf
    import numpy as np
    b, t = eng.ecfg.num_slots, eng.ecfg.num_slots + eng._mixed_budget
    text = jax.jit(lambda p, c, tb, tok, ts, tp, ss, qs, ql, ps:
                   tf.mixed_step(p, cfg, c, tb, tok, ts, tp, ss, qs, ql,
                                 ps)).lower(
        eng.params, eng._cache, jnp.asarray(eng._tables),
        jnp.zeros((t,), jnp.int32), jnp.full((t,), -1, jnp.int32),
        jnp.zeros((t,), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32)).as_text(debug_info=True)
    assert isinstance(np.asarray(eng._tables), np.ndarray)
    for scope in ("arks.embed", "arks.attn_qkv", "arks.attn_kernel",
                  "arks.attn_out", "arks.ffn", "arks.lm_head"):
        assert scope in text, scope


def test_compilation_counters_count_a_fresh_shape_once(monkeypatch):
    _, eng = _mk_engine(monkeypatch)     # depth 0: nothing builds off-thread
    x7, x9 = jnp.ones((7,)), jnp.ones((9,))
    jax.block_until_ready((x7, x9))
    fn = jax.jit(lambda x: x * 3.0 + 1.0)
    n0 = eng.metrics.xla_compilations_total.get()
    s0 = eng.metrics.xla_compile_seconds_total.get()
    fn(x7).block_until_ready()
    assert eng.metrics.xla_compilations_total.get() == n0 + 1
    assert eng.metrics.xla_compile_seconds_total.get() > s0
    fn(x7).block_until_ready()                     # cache hit in-process
    assert eng.metrics.xla_compilations_total.get() == n0 + 1
    fn(x9).block_until_ready()                     # a fresh shape
    assert eng.metrics.xla_compilations_total.get() == n0 + 2
    eng.trace.flush()
    comp = [s for s in eng.trace.phase_spans() if s["name"] == "compile"]
    assert len(comp) >= 2 and all(s["arg"] >= 0 for s in comp)
    text = "\n".join(eng.metrics.registry.render().splitlines())
    assert "xla_compilations_total" in text
    assert "xla_compile_seconds_total" in text


def _churn(eng, cfg, tag, n=5):
    """Prompts of 33 alike tokens, one after another: with no retention
    surplus each evicts the pages the one before left in the index."""
    for i in range(n):
        req = Request(f"{tag}{i}", [(9 + i) % cfg.vocab_size] * 33,
                      SamplingParams(max_tokens=3, temperature=0.0,
                                     ignore_eos=True))
        eng.add_request(req)
        _drive(eng)
        _collect(req)


@pytest.mark.parametrize("warm", [True, False], ids=["warmed", "control"])
def test_the_pools_first_eviction_compiles_nothing_after_warm_up(
        monkeypatch, warm):
    """With the host tier on, the spill gather compiles before the first
    sequential step's dispatch, so the pool's first eviction (inside a
    benchmark window, PERF.md PR 33) adds nothing to
    ``xla_compilations_total``; the control, its warm-up taken out, counts
    the one compilation the warm-up moves.  (The counter is the
    process's: nothing else compiles between the two readings, depth 0
    builds nothing off-thread.)"""
    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "64")
    if not warm:
        monkeypatch.setattr(
            InferenceEngine, "_warm_spill",
            lambda self: setattr(self, "_spill_warm", True))
    cfg, eng = _mk_engine(monkeypatch, prefix_cache_mb=0)
    assert eng._host_tier_on() and not eng._spill_warm
    _churn(eng, cfg, "first", n=1)          # every step program, warm-ups
    assert eng._spill_warm
    assert eng.metrics.prefix_spill_blocks_total.total() == 0
    n0 = eng.metrics.xla_compilations_total.get()
    _churn(eng, cfg, "churn")
    eng._resolve_spills(force=True)
    assert eng.metrics.prefix_spill_blocks_total.total() > 0
    assert eng.metrics.xla_compilations_total.get() == n0 + (not warm)


def test_a_gang_leader_warms_no_spill_gather_and_its_followers_replay(
        monkeypatch):
    """The host tier is single-host (``_host_tier_on``: off under a
    dispatcher), so a leader makes no warm-up gather and broadcasts no op
    for one: a follower fed its op stream replays every op it is sent and
    lands on the leader's pool."""
    import numpy as np

    from arks_tpu.engine.multihost import DispatchFollower

    class Recording:
        def __init__(self):
            self.ops = []

        def broadcast(self, op, payload):
            self.ops.append((op, payload))

    monkeypatch.setenv("ARKS_PREFIX_HOST_MB", "64")
    cfg, leader = _mk_engine(monkeypatch, prefix_cache_mb=0)
    _, feng = _mk_engine(monkeypatch, prefix_cache_mb=0)
    leader.dispatcher = Recording()
    calls = []
    gather = leader._spill_gather_fn
    leader._spill_gather_fn = lambda *a: calls.append(1) or gather(*a)
    _churn(leader, cfg, "l", n=2)
    assert leader._spill_warm and not calls and not leader._host_tier_on()
    names = {op for op, _ in leader.dispatcher.ops}
    assert "mixed" in names and names <= {"mixed", "set_slots", "set_slot"}
    follower = DispatchFollower.__new__(DispatchFollower)
    follower.engine = feng
    follower._jax = jax
    follower._pipe_state = follower._pipe_cols = None
    for op, payload in leader.dispatcher.ops:
        follower._apply(feng, jax, jnp, op, payload)
    np.testing.assert_array_equal(np.asarray(leader._cache.k),
                                  np.asarray(feng._cache.k))


def test_chunk_budget_counter_rises_only_while_a_prompt_can_use_it(
        monkeypatch):
    cfg, eng = _mk_engine(monkeypatch)
    budget = eng._mixed_budget
    assert budget > 0
    _workload(eng, cfg, "w")
    offered = eng.metrics.mixed_chunk_budget_tokens_total.get()
    taken = eng.metrics.mixed_chunk_tokens_total.get()
    assert taken == 3 + 37 + 4
    assert offered >= taken and offered % budget == 0
    steps = eng.metrics.mixed_batch_tokens.count() \
        if hasattr(eng.metrics.mixed_batch_tokens, "count") else None
    # decode-only steps (nothing prefilling, nothing queued) offer nothing
    if steps is not None:
        assert offered < steps * budget


# ----------------------------------------------------------- byte identity

@pytest.mark.parametrize("depth", [0, 2])
def test_streams_identical_with_a_window_open_and_closed(monkeypatch,
                                                         tmp_path, depth):
    cfg, eng = _mk_engine(monkeypatch, depth=depth)
    closed = _workload(eng, cfg, "closed")
    assert eng.profiler.start(str(tmp_path / "p"))["ok"]
    opened = _workload(eng, cfg, "open")
    assert eng.profiler.stop()["ok"]
    again = _workload(eng, cfg, "again")
    assert closed == opened == again
    assert all(reason == "length" for _, reason in closed)
