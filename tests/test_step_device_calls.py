"""The sequential step speaks to the device twice: its one program (the host
operands ride the dispatch as ONE buffer) and ONE promotion program for all
prompts that completed in it.

On the CPU, at ``tiny`` size, depth 0 (every step sequential), plain and
speculative engine: the counter ``step_device_calls_total{site}`` against
the dispatch count, a counting wrapper around the engine module's ``jnp`` /
``jax`` (nothing eager between two steps), ``xla_compilations_total`` flat
once the promotion program's sizes are warm, the folded key bit for bit
``jax.random.fold_in(PRNGKey(seed), 1)``, and streams byte-identical to
those recorded through ``_apply_set_slot``'s one-slot form.
"""

import jax
import numpy as np
import pytest

from arks_tpu.engine import (EngineConfig, InferenceEngine, Request,
                             SamplingParams)
from arks_tpu.engine import engine as engine_mod
from arks_tpu.engine.tokenizer import ByteTokenizer
from arks_tpu.models import get_config

GUIDE = ("regex", "[a-f]+")
# What the eager forms were made of; the static twin of this list is
# arkslint's ``eager-device-call``.
_JNP_CALLS = ("asarray", "array", "zeros", "ones", "full", "arange", "where",
              "stack", "concatenate", "pad")


class _Counted:
    """``mod`` with the listed callables (all of them where ``names`` is
    None) logging each call before it runs."""

    def __init__(self, mod, log, label, names=None, children=()):
        self._mod, self._log, self._label = mod, log, label
        self._names, self._children = names, dict(children)

    def __getattr__(self, name):
        if name in self._children:
            return self._children[name]
        attr = getattr(self._mod, name)
        if callable(attr) and (self._names is None or name in self._names):
            def counted(*a, **kw):
                self._log.append(f"{self._label}.{name}")
                return attr(*a, **kw)
            return counted
        return attr


def _params(kind: str, seed: int, max_tokens: int = 6) -> SamplingParams:
    """One lane of each shaping kind; seeded so that two runs sample alike."""
    base = dict(max_tokens=max_tokens, ignore_eos=True, seed=seed)
    return {
        "greedy_bias_min": SamplingParams(
            temperature=0.0, logit_bias=((65, 4.0), (66, -3.0)),
            min_tokens=3, **base),
        "sampled_penalties": SamplingParams(
            temperature=0.9, top_p=0.9, top_k=20, presence_penalty=0.5,
            frequency_penalty=0.3, **base),
        "sampled_guide": SamplingParams(
            temperature=0.8, guide=GUIDE, **{**base, "ignore_eos": False}),
        "greedy": SamplingParams(temperature=0.0, **base),
        "sampled": SamplingParams(temperature=1.0, top_p=0.95, **base),
    }[kind]


# (prompt length, lane kind): three prompts that fit ONE step's chunk budget
# of 16 tokens together, so all three complete in the same step.
THREE = ((4, "greedy_bias_min"), (5, "sampled_penalties"), (6, "sampled_guide"))
ONE = ((7, "sampled_penalties"),)


def _traffic(tag: str, lanes, shift: int) -> list[Request]:
    return [Request(f"{tag}-{i}", [(11 * i + shift + j) % 200 + 3
                                   for j in range(n)],
                    _params(kind, seed=1000 + 17 * i + shift))
            for i, (n, kind) in enumerate(lanes)]


class _Driven:
    """An engine stepped by hand, with what each step did on record."""

    def __init__(self, spec: bool):
        mp = pytest.MonkeyPatch()
        mp.setenv("ARKS_MIXED_STEP", "auto")
        self.spec = spec
        kw = dict(model="tiny", num_slots=4, max_cache_len=64,
                  prefill_buckets=(8, 16, 32), steps_per_dispatch=4,
                  prefill_chunk=16, kv_layout="paged")
        if spec:
            kw.update(draft_model="tiny", draft_len=3)
        try:
            self.eng = InferenceEngine(get_config("tiny"),
                                       EngineConfig(**kw), ByteTokenizer())
        finally:
            mp.undo()
        self.completed: list[int] = []   # prompts promoted, per promotion
        self.keys: list[tuple] = []      # (seed, key row after promotion)
        inner = self.eng._promote_completing

        def recording(completing, *a):
            inner(completing, *a)
            if completing:
                self.completed.append(len(completing))
                rows = np.asarray(self.eng._sampling.key)
                self.keys += [(st.seed, rows[slot].copy())
                              for slot, st, _, _ in completing]
        self.eng._promote_completing = recording

    def calls(self) -> dict:
        c = self.eng.metrics.step_device_calls_total
        return {site: c.get(site=site) for site in
                ("step", "promote", "admit", "clear", "draft", "warm")}

    def dispatches(self) -> int:
        data = self.eng.metrics.mixed_batch_tokens._data
        return data[()][2] if () in data else 0

    def run(self, reqs) -> tuple[list, list]:
        """Drive ``reqs`` to their end.  Returns (streams, steps): per step
        (dispatches, calls by site, prompts completed)."""
        eng = self.eng
        for r in reqs:
            eng.add_request(r)
        steps = []
        for _ in range(3000):
            d0, c0, n0 = self.dispatches(), self.calls(), len(self.completed)
            eng.step(block_s=0.01)
            c1 = self.calls()
            steps.append((self.dispatches() - d0,
                          {k: c1[k] - c0[k] for k in c1},
                          sum(self.completed[n0:])))
            if eng.idle:
                break
        assert eng.idle, "the engine did not drain"
        streams = []
        for r in reqs:
            ids = []
            while True:
                out = r.outputs.get(timeout=60)
                ids.extend(out.token_ids)
                if out.finished:
                    assert out.finish_reason in ("length", "stop"), out
                    break
            streams.append(ids)
        return streams, steps


@pytest.fixture(scope="module", params=["plain", "spec"])
def driven(request):
    d = _Driven(spec=request.param == "spec")
    # Warm-up: every program and guide the cases below use.
    d.run(_traffic("warm3", THREE, shift=1))
    d.run(_traffic("warm1", ONE, shift=2))
    yield d
    d.eng.stop()


@pytest.mark.parametrize("n_complete", [0, 1, 3])
def test_two_device_calls_a_step_and_none_eager(driven, monkeypatch,
                                                n_complete):
    d = driven
    assert d.calls()["step"] > 0 and d.dispatches() > 0   # the warm-up ran
    log: list[str] = []
    monkeypatch.setattr(engine_mod, "jnp", _Counted(
        engine_mod.jnp, log, "jnp", _JNP_CALLS))
    monkeypatch.setattr(engine_mod, "jax", _Counted(
        engine_mod.jax, log, "jax", ("device_put",),
        children={"random": _Counted(jax.random, log, "jax.random")}))
    compiles0 = d.eng.metrics.xla_compilations_total.get()
    lanes = THREE if n_complete == 3 else ONE
    _, steps = d.run(_traffic(f"c{n_complete}", lanes, shift=20 + n_complete))
    assert log == [], log
    # Every size of the promotion program compiled at warm-up.
    assert d.eng.metrics.xla_compilations_total.get() == compiles0
    seen = [s for s in steps if s[0] and s[2] == n_complete]
    assert seen, steps
    for n_disp, by_site, done in steps:
        assert n_disp <= 1
        assert by_site["step"] == n_disp
        assert by_site["promote"] == (1 if done else 0)
        assert by_site["step"] + by_site["promote"] <= 2
        assert by_site["warm"] == by_site["admit"] == 0
        # A speculative engine prefills its draft cache once a completed
        # prompt, a program of its own (bucketed by prompt length); a plain
        # engine has none.
        assert by_site["draft"] == (done if d.spec else 0)
    total = {k: sum(s[1][k] for s in steps) for k in steps[0][1]}
    n_disp = sum(s[0] for s in steps)
    assert (total["step"] + total["promote"]) / n_disp <= 2.0


def test_promotion_sizes_compile_at_warm_up_only():
    """A fresh engine: the first sequential step compiles the promotion
    program once a size, counted under ``warm``; later promotions of any
    size compile nothing."""
    d = _Driven(spec=False)
    try:
        sizes = tuple(d.eng._promote_packs)
        assert sizes == (1, 4)         # 4 slots: a step completes at most 4
        assert d.calls()["warm"] == 0
        d.run(_traffic("w", ONE, shift=3))
        assert d.calls()["warm"] == len(sizes)
        assert d.eng.compiled_program_variants()["_promote_fn"] == len(sizes)
        d.run(_traffic("w3", THREE, shift=4))   # guide, bias, penalties
        compiles = d.eng.metrics.xla_compilations_total.get()
        d.run(_traffic("x3", THREE, shift=5))   # 3 rows padded to 4
        d.run(_traffic("x2", THREE[:2], shift=6))
        assert d.eng.metrics.xla_compilations_total.get() == compiles
        assert d.calls()["warm"] == len(sizes)
        assert d.eng.compiled_program_variants()["_promote_fn"] == len(sizes)
    finally:
        d.eng.stop()


def test_folded_key_is_fold_in_of_the_seed(driven):
    d = driven
    d.keys.clear()
    d.run(_traffic("k", THREE, shift=40))
    assert len(d.keys) == 3
    for seed, row in d.keys:
        want = np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), 1))
        assert np.array_equal(row, want), (seed, row, want)


@pytest.mark.parametrize("lanes", [
    pytest.param(THREE, id="shaped-three-in-a-step"),
    pytest.param(((4, "greedy"), (5, "greedy"), (6, "greedy")),
                 id="greedy"),
    pytest.param(((4, "sampled"), (5, "sampled_penalties"), (3, "sampled")),
                 id="seeded-sampled"),
])
def test_streams_equal_the_one_slot_form(driven, monkeypatch, lanes):
    """The same requests, the same seeds: one promotion program for the
    step's prompts against ``_apply_set_slot`` once a prompt."""
    d = driven
    eng = d.eng
    batched, steps = d.run(_traffic("b", lanes, shift=60))
    assert max(s[2] for s in steps) == 3

    def one_by_one(completing, ids, want_lp, lp_host):
        for slot, st, gid, grow0 in completing:
            first = int(ids[slot])
            grow1 = eng.guides.next_row(grow0, first) if gid >= 0 else 0
            eng._apply_set_slot(slot, st.request.params, st.key, True,
                                num_prompt=len(st.ids), guide=gid,
                                guide_row=grow1, site="promote")
            del eng._prefilling[slot]
            eng._register_slot(st.request, slot, first, len(st.ids),
                               seed=st.seed)
    monkeypatch.setattr(eng, "_promote_completing", one_by_one)
    single, steps1 = d.run(_traffic("s", lanes, shift=60))
    assert max(s[1]["promote"] for s in steps1) == 3
    assert single == batched
    assert all(len(s) > 0 for s in batched)


class _Recording:
    def __init__(self):
        self.ops = []

    def broadcast(self, op, payload):
        self.ops.append((op, payload))


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_follower_replays_the_packed_step_and_the_promotion(spec):
    """A follower fed the leader's op stream (``mixed`` / ``spec_mixed`` with
    the batch by name, ``set_slots`` with a step's promotions and, without
    rows, the warm-up of a size) makes the same calls of the same programs
    and lands on the leader's device state."""
    import jax.numpy as jnp
    from arks_tpu.engine.multihost import DispatchFollower

    leader, feng = _Driven(spec), _Driven(spec)
    try:
        leader.eng.dispatcher = _Recording()
        leader.run(_traffic("l", THREE, shift=70))
        ops = leader.eng.dispatcher.ops
        names = [op for op, _ in ops]
        sizes = len(leader.eng._promote_packs)
        rows = [len(p["rows"]) for op, p in ops if op == "set_slots"]
        # The warm-up of each size, then the promotions (the guided lane
        # may complete a step later, once its guide has compiled).
        assert rows[:sizes] == [0] * sizes and sum(rows) == 3, rows
        assert ("spec_mixed" if spec else "mixed") in names
        follower = DispatchFollower.__new__(DispatchFollower)
        follower.engine = feng.eng
        follower._jax = jax
        follower._pipe_state = None
        follower._pipe_cols = None
        for op, payload in ops:
            follower._apply(feng.eng, jax, jnp, op, payload)
        for name in ("key", "temperature", "top_k", "bias_ids", "bias_vals",
                     "suppress_ids", "min_until", "guide_row"):
            np.testing.assert_array_equal(
                np.asarray(getattr(leader.eng._sampling, name)),
                np.asarray(getattr(feng.eng._sampling, name)), err_msg=name)
        np.testing.assert_array_equal(np.asarray(leader.eng._cache.k),
                                      np.asarray(feng.eng._cache.k))
    finally:
        leader.eng.stop()
        feng.eng.stop()
